"""Multi-host smoke run: jax.distributed over N local processes (ROADMAP).

Boots a real `jax.distributed` cluster out of N co-located processes (each
with forced host devices) and trains a tiny `split_mode="hist"` forest
through the SAME `build_forest` + `ShardedHistNumeric` path the
single-process mesh tests exercise, asserting equality with the
single-process (local-engine) result.

Two modes, picked automatically:

  * ``global``  — the mesh spans ALL processes' devices and the engine's
    psum crosses process boundaries.  This is the true multi-host path;
    it requires a backend with cross-process collectives (TPU, GPU).
  * ``local-mesh`` — the CPU backend in current jax releases rejects
    cross-process computations ("Multiprocess computations aren't
    implemented on the CPU backend"), so each process falls back to a
    mesh over its OWN devices.  The smoke still proves the parts a CPU
    box can prove: the distributed service boots and every process's
    sharded-hist forest is bit-identical to the local reference and to
    every other process (fingerprints compared by the launcher).

The workers run on the CPU backend only (the launcher sets
``JAX_PLATFORMS=cpu`` for them): N processes cannot share one chip, and a
worker refuses any other backend.  On a TPU host, the sharded engines run
in ONE process over the chips present (``chip_smoke.py --chips 4``).

Run:  python -m repro.launch.multihost_smoke [--nproc N]
Test: tests/test_multihost_smoke.py (-m slow).

Each worker prints ``MULTIHOST-SMOKE-OK mode=<mode> pid=<i> fp=<sha1>``;
the launcher asserts N OKs and identical fingerprints.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import sys

_PORT = int(os.environ.get("MULTIHOST_SMOKE_PORT", "12731"))
_DEVS_PER_PROC = 4


def _forest_fingerprint(forest) -> str:
    """Order-stable digest of every tree's flat arrays."""
    import numpy as np
    h = hashlib.sha1()
    for t in forest.trees:
        for name in ("feature", "threshold", "is_cat", "cat_mask",
                     "children", "value", "n_node", "gain", "depth"):
            h.update(np.ascontiguousarray(getattr(t, name)).tobytes())
    return h.hexdigest()


def _train(mesh) -> tuple[str, object]:
    """(fingerprint of the sharded-hist forest, local reference forest)."""
    import numpy as np

    from repro.core import tree as tree_lib
    from repro.core.dataset import from_numpy
    from repro.core.forest import RandomForest
    from repro.core.level.sharded import ShardedHistNumeric

    rng = np.random.default_rng(7)
    n = 512
    num = rng.normal(size=(n, 8)).astype(np.float32)
    y = ((num[:, 0] + num[:, 1] * num[:, 2]) > 0).astype(np.int32)
    ds = from_numpy(num, None, y)
    p = tree_lib.TreeParams(max_depth=3, leaf_pad=8, split_mode="hist",
                            num_bins=16)
    local = RandomForest(p, num_trees=2, seed=11, tree_batch=2).fit(ds)
    eng = ShardedHistNumeric(mesh=mesh)
    dist = RandomForest(p, num_trees=2, seed=11, tree_batch=2).fit(
        ds, engine=eng)
    a, b = _forest_fingerprint(local), _forest_fingerprint(dist)
    assert a == b, "sharded-hist forest != single-process local forest"
    return a, dist


def worker(pid: int, nproc: int) -> None:
    import jax
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"multihost_smoke workers run on forced CPU host devices, not "
            f"{jax.default_backend()!r}: N processes cannot share a chip. "
            f"Run the sharded engines in one process on the chips "
            f"present instead (chip_smoke.py --chips 4).")
    jax.distributed.initialize(
        coordinator_address=f"localhost:{_PORT}",
        num_processes=nproc, process_id=pid)
    assert len(jax.devices()) == nproc * _DEVS_PER_PROC, (
        len(jax.devices()), nproc)

    import numpy as np
    from jax.sharding import Mesh

    mode = "global"
    try:
        mesh = Mesh(np.asarray(jax.devices()).reshape(
            nproc, _DEVS_PER_PROC), ("data", "model"))
        fp, _ = _train(mesh)
    except Exception as e:                       # noqa: BLE001
        if "Multiprocess computations" not in str(e):
            raise
        # CPU backend: no cross-process collectives — prove the rest on a
        # process-local mesh (the launcher still checks cross-process
        # determinism through the fingerprints)
        mode = "local-mesh"
        local_devs = jax.local_devices()
        mesh = Mesh(np.asarray(local_devs).reshape(
            2, _DEVS_PER_PROC // 2), ("data", "model"))
        fp, _ = _train(mesh)
    print(f"MULTIHOST-SMOKE-OK mode={mode} pid={pid} fp={fp}", flush=True)


def main(nproc: int = 2, timeout: float = 900.0) -> dict:
    """Spawn the workers, collect and validate their output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{_DEVS_PER_PROC}")
    procs = [subprocess.Popen(
        [sys.executable, "-m", "repro.launch.multihost_smoke",
         "--worker", str(i), str(nproc)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for i in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
    finally:
        # a failed/timed-out worker must not orphan its peers: they sit in
        # jax.distributed.initialize holding the coordinator port, which
        # would wedge every later run against the same port
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    oks = [line for out in outs for line in out.splitlines()
           if line.startswith("MULTIHOST-SMOKE-OK")]
    assert len(oks) == nproc, outs
    fps = {line.split("fp=")[1] for line in oks}
    assert len(fps) == 1, f"processes disagree: {oks}"
    mode = oks[0].split("mode=")[1].split()[0]
    print(f"multihost smoke: {nproc} processes OK, mode={mode}, "
          f"fingerprint {fps.pop()[:12]}")
    return {"nproc": nproc, "mode": mode}


if __name__ == "__main__":
    if "--worker" in sys.argv:
        i = sys.argv.index("--worker")
        worker(int(sys.argv[i + 1]), int(sys.argv[i + 2]))
    else:
        n = 2
        if "--nproc" in sys.argv:
            n = int(sys.argv[sys.argv.index("--nproc") + 1])
        main(n)
