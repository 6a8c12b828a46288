"""Share of the level programs' device time spent in collectives: the
device seconds of the collective operations (`all-gather`, `all-reduce`,
`reduce-scatter`, `collective-permute`, `all-to-all`, with their
`-start` / `-done` halves) inside the programs named `*fused_level_step*`
(`core/level/plan.py`) over those programs' device seconds, both per chip
as `trace_reduce` averages them.  On a mesh the exact engine's merge is
an `all_gather` over the row shards (`core/level/sharded.py`); a program
with no collective reads 0, and a run with no level program nothing."""
import re

NAME = "fused_level_step"
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?"
                        r"([.-]|$)")


def read(run):
    s = run.get("trace")
    if s is None:
        return None
    secs = sum(v["seconds"] for k, v in s["modules"].items() if NAME in k)
    if secs <= 0:
        return None
    coll = sum(v for k, v in s["ops"].items()
               if NAME in k.split(":", 1)[0]
               and COLLECTIVE.match(k.split(":", 1)[1]))
    return 100.0 * coll / secs
