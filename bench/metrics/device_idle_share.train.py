"""Share of the traced training window in which no operation ran on the
device (profiler trace: 1 - union of op intervals / window).  Most of it
is host work between and around the level programs (`core/tree.py`
`build_forest` bookkeeping, `forest.pack_trees`, presort dispatch)."""


def read(run):
    s = run.get("trace")
    if run["kind"] != "train" or s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
