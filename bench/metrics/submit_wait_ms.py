"""Mean milliseconds from entry to ``ServeFront.add_request`` until
``Engine.submit`` returned, over the requests submitted in the window: the
wait for the front's lock and for the engine's, which a running step holds
(``serve_submit_wait_seconds`` sum and count from ``/v1/metrics``)."""


def read(run):
    n = run.counter_delta("serve_submit_wait_seconds_count")
    if not n:
        return None
    return run.counter_delta("serve_submit_wait_seconds_sum") / n * 1e3
