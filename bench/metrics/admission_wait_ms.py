"""Mean milliseconds a request waited in the engine's queue before
admission gave it a slot, over the requests admitted in the window
(``engine_admission_wait_seconds`` sum and count from ``/v1/metrics``)."""


def read(run):
    n = run.counter_delta("engine_admission_wait_seconds_count")
    if not n:
        return None
    return run.counter_delta("engine_admission_wait_seconds_sum") / n * 1e3
