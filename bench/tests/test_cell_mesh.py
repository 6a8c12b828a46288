"""The four-chip cell's path on four forced host devices: a run of the
`higgs.exact.4chip` traffic, cut small, reads `correct: true`, its trees
equal those of the same traffic without `mesh`, and with the timed path
broken underneath it reads `correct: false`, once for each fault a cell
on a mesh can have.  Each case runs in a child process of its own, so
that this process keeps its one device."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
SRC = BENCH.parent / "src"

PRELUDE = f"""
import json, sys
sys.path[:0] = [{str(BENCH)!r}, {str(BENCH / "tests")!r}]
import jax
import numpy as np
import pytest
import cell_small, run
assert len(jax.devices()) == 4

SEED = 2 ** 31 + 41
SMALL = dict(rows=4096)

def run_cell(mesh=True, devices=4):
    cell = cell_small.load("higgs.exact.4chip", **SMALL)
    if not mesh:
        cell["traffic"].pop("mesh")
    return run.run_cell("higgs.exact.4chip", SEED, 0.2, 0,
                        devices=jax.devices()[:devices], cell=cell)
"""


def _child(code: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(SRC))
    out = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_run_is_correct_and_its_trees_equal_one_devices():
    res = _child("""
        from repro.core.forest import RandomForest
        fit, fits = RandomForest.fit, []

        def spy(self, ds, *a, **kw):
            out = fit(self, ds, *a, **kw)
            fits.append((self.seed, kw.get("engine") is not None,
                         self.trees))
            return out
        RandomForest.fit = spy
        mesh, one = run_cell(), run_cell(mesh=False, devices=1)
        by_seed = {seed: trees for seed, sharded, trees in fits
                   if not sharded}
        same = 0
        for seed, sharded, trees in fits:
            if not sharded or seed not in by_seed:
                continue
            for a, b in zip(trees, by_seed[seed]):
                for f in ("feature", "threshold", "children", "n_node",
                          "value", "depth"):
                    np.testing.assert_array_equal(getattr(a, f),
                                                  getattr(b, f))
            same += 1
        print(json.dumps({"mesh": mesh["correct"], "one": one["correct"],
                          "sharded_fits": sum(s for _, s, _ in fits),
                          "compared": same, "checks": mesh["checks"]}))
    """)
    assert res["mesh"] and res["one"], res
    assert res["sharded_fits"] >= 2 and res["compared"] >= 2, res


NO_EXCHANGE = """
    def no_exchange(x, axis_name, **kw):
        # each row shard sees only its own partial state, as if the
        # all_gather over the row axis had been left out
        size = jax.lax.axis_size(axis_name)
        return jax.numpy.broadcast_to(x[None], (size,) + x.shape)
    jax.lax.all_gather = no_exchange
"""


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer", "no_exchange"])
def test_broken_mesh_run_is_not_correct(fault):
    if fault == "no_exchange":
        plant = NO_EXCHANGE
    else:
        plant = f"""
            import test_cell_faults_train as faults
            faults.FAULTS[{fault!r}](pytest.MonkeyPatch())
        """
    res = _child(textwrap.dedent(plant) + textwrap.dedent("""
        line = run_cell()
        print(json.dumps({"correct": line["correct"],
                          "checks": line["checks"]}))
    """))
    assert not res["correct"], res
