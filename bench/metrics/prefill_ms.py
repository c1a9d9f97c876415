"""Mean milliseconds from admission until the step whose sync hands the
host the request's first sampled token, over the requests whose first
token came in the window (``engine_prefill_seconds`` sum and count from
``/v1/metrics``)."""


def read(run):
    n = run.counter_delta("engine_prefill_seconds_count")
    if not n:
        return None
    return run.counter_delta("engine_prefill_seconds_sum") / n * 1e3
