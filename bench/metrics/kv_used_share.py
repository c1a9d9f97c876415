"""Share of the KV rows reserved at admission that the active requests
hold, summed over the window's steps (``engine_kv_rows_used_total`` over
``engine_kv_rows_reserved_total``, deltas from ``/v1/metrics``): how much of
the worst-case reservation, prompt plus every output token, a request
occupies on average while it runs."""


def read(run):
    reserved = run.counter_delta("engine_kv_rows_reserved_total")
    if not reserved:
        return None
    return 100.0 * run.counter_delta("engine_kv_rows_used_total") / reserved
