"""Share of the traced serving window in which no operation ran on the
device (profiler trace: 1 - union of op intervals / window): waiting for
arrivals plus the server's host path (validate, transfer, dispatch)."""


def read(run):
    s = run.get("trace")
    if run["kind"] != "serve" or s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
