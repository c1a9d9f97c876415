"""Mean per-step token budget over the window's steps: what
``core/scheduler.step_token_budget`` gave each step as Algorithm 2's
``npu_fraction`` moved (``engine_step_token_budget`` sum and count from
``/v1/metrics``)."""


def read(run):
    n = run.counter_delta("engine_step_token_budget_count")
    if not n:
        return None
    return run.counter_delta("engine_step_token_budget_sum") / n
