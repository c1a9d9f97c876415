"""The FFN's share of its roofline: the least time the window's steps'
FFNs could take on this chip (``bench/layer_counts.py``: the larger of
their operations over the bf16 peak and their int8 weights, parity and
scales over HBM bandwidth), over the device time under the ``ffn`` scope
while those steps ran (``bench/scopes.py``; its nested ECC included)."""

from bench import layer_counts, scopes


def read(run):
    red = scopes.of_run(run)
    if red is None or not run.steps or not red["scopes"].get("ffn"):
        return None
    least = sum(layer_counts.ffn_least_seconds(run.sizes, s.chunks,
                                            run.peaks)
                for s in run.steps)
    return 100.0 * least / red["scopes"]["ffn"]
