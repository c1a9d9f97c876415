"""Frontend share of time to first token: the mean TTFT the clients saw
minus the mean of ServeFront's own ``serve_ttft_seconds`` over the same
window (sum and count from ``/v1/metrics``, before and after)."""


def read(run):
    n = run.counter_delta("serve_ttft_seconds_count")
    firsts = [r.t_tokens[0] - r.t_send for r in run.records if r.t_tokens]
    if not n or not firsts:
        return None
    server_mean = run.counter_delta("serve_ttft_seconds_sum") / n
    return (sum(firsts) / len(firsts) - server_mean) * 1e3
