"""Sharded training through the batched builder (ISSUE 4 tentpole bench).

Before the SplitEngine refactor, distributed training was the ONE
configuration that lost the multi-tree batch amortization: `fit` routed a
`supersplit_fn` to the per-tree builder, T·D level programs per forest.
This benchmark trains the same forest on a 2×4 forced-host-device mesh
(data × model, the distributed test topology) through BOTH paths —
`tree_batch=1` (per-tree, one mesh program per depth PER TREE) and
`tree_batch=T` (batched, one mesh program per depth for ALL trees) — for
the exact AND the histogram engine, verifies bit-identical forests, and
records the programs-per-depth counts and fit walls to
``BENCH_dist_batch.json``.  The acceptance signal is `level_programs_
batched == D` (not T·D) for every sharded configuration.

On a TPU the workload runs in this process on a mesh of the chips
present.  Elsewhere it runs in a CPU-only SUBPROCESS on 8 forced host
devices, so the forced device count never leaks into the parent (same
pattern as tests/test_distributed.py).  Smoke mode shrinks n/T/depth to
seconds-scale.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

OUT_PATH = os.environ.get("BENCH_DIST_BATCH_JSON", "BENCH_dist_batch.json")


def measure(mesh, n: int, n_trees: int, depth: int) -> list[dict]:
    """Fit the forest per-tree and batched on `mesh`, for exact and hist;
    check both against the local batched fit; one row per mode."""
    import time

    import numpy as np

    from repro import obs
    from repro.core import distributed, tree as tree_lib
    from repro.core.dataset import from_numpy
    from repro.core.forest import RandomForest

    rng = np.random.default_rng(7)
    num = rng.normal(size=(n, 8)).astype(np.float32)
    y = ((num[:, 0] + num[:, 1] * num[:, 2]) > 0).astype(np.int32)
    ds = from_numpy(num, None, y)

    def fit_timed(params, engine, tree_batch):
        RandomForest(params, num_trees=n_trees, seed=10,
                     tree_batch=tree_batch).fit(ds, engine=engine)  # warm
        best, rf, programs = float('inf'), None, 0
        for rep in (1, 2):
            c0 = (obs.counter("level.tree_dispatches"),
                  obs.counter("level.dispatches"))
            t0 = time.perf_counter()
            out = RandomForest(params, num_trees=n_trees, seed=10,
                               tree_batch=tree_batch).fit(ds, engine=engine)
            dt = time.perf_counter() - t0
            if rep == 1:
                rf = out
                programs = (obs.counter("level.tree_dispatches") - c0[0]
                            + obs.counter("level.dispatches") - c0[1])
            best = min(best, dt)
        return best, rf, programs

    configs = [
        ('exact', tree_lib.TreeParams(max_depth=depth),
         distributed.make_2d_sharded_supersplit(mesh)),
        ('hist', tree_lib.TreeParams(max_depth=depth, split_mode='hist',
                                     num_bins=64),
         distributed.make_hist_sharded_supersplit(mesh)),
    ]
    rows = []
    for mode, params, engine in configs:
        local_rf = RandomForest(params, num_trees=n_trees, seed=10,
                                tree_batch=n_trees).fit(ds)
        per_s, per_rf, per_prog = fit_timed(params, engine, 1)
        bat_s, bat_rf, bat_prog = fit_timed(params, engine, n_trees)
        D = max(t.max_depth_reached for t in bat_rf.trees)
        for ta, tb, tc in zip(local_rf.trees, per_rf.trees, bat_rf.trees):
            np.testing.assert_array_equal(ta.feature, tb.feature)
            np.testing.assert_array_equal(ta.feature, tc.feature)
            np.testing.assert_array_equal(ta.threshold, tc.threshold)
            np.testing.assert_array_equal(ta.value, tc.value)
        rows.append(dict(
            mode=mode, n=n, n_trees=n_trees, max_depth=depth,
            deepest_tree=D,
            per_tree_s=round(per_s, 4), batched_s=round(bat_s, 4),
            speedup=round(per_s / bat_s, 3) if bat_s else None,
            level_programs_per_tree=per_prog,
            level_programs_batched=bat_prog,
            bit_identical_to_local=True))
    return rows


def _host_mesh_rows(n: int, n_trees: int, depth: int) -> list[dict]:
    """`measure` on a 2x4 forced-host-device mesh, in a CPU-only child
    process so the forced device count never leaks into this one."""
    code = ("import json\n"
            "from benchmarks.dist_batch_bench import measure\n"
            "from repro.launch.mesh import make_host_mesh\n"
            f"rows = measure(make_host_mesh(2, 4), {n}, {n_trees}, {depth})\n"
            "print('JSON::' + json.dumps(rows))\n")
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(root, "src"), root,
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"dist bench subprocess failed:\n"
                           f"{out.stderr[-3000:]}")
    return json.loads(
        next(l for l in out.stdout.splitlines()
             if l.startswith("JSON::"))[len("JSON::"):])


def run(smoke: bool = False):
    import jax

    from repro.launch.mesh import make_host_mesh
    n, n_trees, depth = (1024, 4, 4) if smoke else (8192, 8, 6)
    if jax.default_backend() == "tpu":
        # one process holds the chips: measure in process on the mesh of
        # the chips present (never a child that would need them too)
        mesh = make_host_mesh()
        rows = measure(mesh, n, n_trees, depth)
        mesh_desc = (f"{mesh.devices.shape[0]}x{mesh.devices.shape[1]} "
                     f"{jax.devices()[0].device_kind} (data x model)")
    else:
        rows = _host_mesh_rows(n, n_trees, depth)
        mesh_desc = "2x4 host devices (data x model)"
    for r in rows:
        assert r["level_programs_batched"] < r["level_programs_per_tree"]
        assert r["level_programs_batched"] <= r["max_depth"] + 1
        emit(f"dist_batch/{r['mode']}/batched/n{r['n']}",
             r["batched_s"] * 1e6,
             f"programs={r['level_programs_batched']};"
             f"speedup=x{r['speedup']:.2f}")
    report = {
        "workload": {"mesh": mesh_desc, "m_num": 8,
                     "backend": "segment",
                     "cpu_count": os.cpu_count()},
        "configs": rows,
        "smoke": smoke,
        "note": ("same sharded forest trained per-tree (tree_batch=1, T*D "
                 "mesh programs) vs batched (tree_batch=T, D programs — "
                 "the ISSUE 4 acceptance shape); forests verified "
                 "bit-identical to the LOCAL batched builder for exact and "
                 "hist engines; walls are host-clock times on the mesh "
                 "named above"),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    emit("dist_batch/json", 0.0, OUT_PATH)
    return report


def main() -> None:
    run(smoke="--smoke" in sys.argv)


if __name__ == "__main__":
    main()
