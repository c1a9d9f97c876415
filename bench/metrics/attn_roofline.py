"""Attention's share of its roofline: the least time the window's steps'
attention could take on this chip (``bench/layer_counts.py``: the bf16
projections, the KV rows read and written, the score and value products;
the duplicate int8 projection Algorithm 2 may run is not credited), over
the device time under the ``attn`` scope while those steps ran
(``bench/scopes.py``)."""

from bench import layer_counts, scopes


def read(run):
    red = scopes.of_run(run)
    if red is None or not run.steps or not red["scopes"].get("attn"):
        return None
    least = sum(layer_counts.attn_least_seconds(run.sizes, s.chunks,
                                            run.peaks)
                for s in run.steps)
    return 100.0 * least / red["scopes"]["attn"]
