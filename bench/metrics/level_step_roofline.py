"""The level step's share of its roofline: the least time of the traced
level programs (`bench/roofline.py` bytes over the HBM bandwidth of the
run's chips together) over the device time of the programs named
`*fused_level_step*` in the trace (`core/level/plan.py`
`_fused_level_step[_batched]`), which `trace_reduce` averages over the
chips.  On one chip the divisor is that chip's bandwidth."""

NAME = "fused_level_step"


def read(run):
    s = run.get("trace")
    nbytes = run.get("level_bytes", 0)
    if s is None or nbytes <= 0:
        return None
    secs = sum(v["seconds"] for k, v in s["modules"].items() if NAME in k)
    if secs <= 0:
        return None
    bandwidth = run["device"]["count"] * run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / secs
