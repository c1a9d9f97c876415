"""Share of the device's busy time, while the window's steps ran, spent
under the ``ecc`` scope: the Hamming syndrome and correction passes over
every flash-tier weight a step reads (``core/ecc.py``), wherever they run
(``bench/scopes.py``)."""

from bench import scopes


def read(run):
    red = scopes.of_run(run)
    if red is None or red["busy_s"] <= 0 or not red["scopes"].get("ecc"):
        return None
    return 100.0 * red["scopes"]["ecc"] / red["busy_s"]
