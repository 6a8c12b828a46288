"""A cell of `BENCHMARK.json` cut small enough to run on the CPU, for the
tests that drive a whole run below the harness's look for a chip."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH / "configs")]

SMALL = {
    "train": {"rows": 3000, "num_trees": 2, "check_trees": 2},
    "serve": {"rows": 4096, "num_trees": 6, "depth": 6, "rate": 400,
              "check_requests": 200},
}


def load(workload, **override):
    import harness
    cell = harness.load_cell(workload)
    kind = cell["traffic"]["kind"]
    cell["traffic"].update(SMALL[kind], **override)
    if kind == "train":
        cell["traffic"]["tree_params"] = dict(
            cell["traffic"]["tree_params"], max_depth=6, leaf_pad=8)
    return cell


def run(workload, seed=2 ** 31 + 11, seconds=0.3, **override):
    import jax

    import run as run_mod
    return run_mod.run_cell(workload, seed, seconds, 0,
                            devices=jax.devices()[:1],
                            cell=load(workload, **override))
