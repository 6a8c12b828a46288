"""The level step's share of its roofline: the least time of the traced
level programs (`bench/roofline.py` bytes over the chip's HBM bandwidth)
over the device time of the programs named `*fused_level_step*` in the
trace (`core/level/plan.py` `_fused_level_step[_batched]`)."""

NAME = "fused_level_step"


def read(run):
    s = run.get("trace")
    nbytes = run.get("level_bytes", 0)
    if s is None or nbytes <= 0:
        return None
    secs = sum(v["seconds"] for k, v in s["modules"].items() if NAME in k)
    if secs <= 0:
        return None
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / secs
