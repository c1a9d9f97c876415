"""Host milliseconds per engine step in the phases that do not block on
the device or the weight stream: every ``engine_step_seconds`` phase but
``sync`` (the device->host read of the sampled tokens), ``stream_wait``
(the wait for a weight window) and the ``total`` of all phases. On the
resident plane that is ``plan`` + ``dispatch``."""

BLOCKING = ("sync", "stream_wait", "total")
PREFIX = 'engine_step_seconds_sum{phase="'


def read(run):
    steps = run.counter_delta('engine_step_seconds_count{phase="total"}')
    if not steps:
        return None
    host = sum(run.counter_delta(key) for key in run.server_after
               if key.startswith(PREFIX)
               and key[len(PREFIX):-2] not in BLOCKING)
    return host / steps * 1e3
