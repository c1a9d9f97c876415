"""Share of the device's busy time, while the measured steps ran, spent
under the ``route`` scope: router logits and top-k, the sort of the
assignments by slab row, the gather of their inputs and the gate-weighted
combine (``bench/moe_scopes.py``)."""

from bench import moe_scopes


def read(run):
    red = moe_scopes.of_run(run)
    if red is None or red["busy_s"] <= 0 or not red["scopes"].get("route"):
        return None
    return 100.0 * red["scopes"]["route"] / red["busy_s"]
