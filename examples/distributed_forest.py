"""Distributed DRF: train the SAME forest with the 2-D sharded supersplit
engine (feature columns over "model" splitters, presorted rows over "data")
and verify it is bit-identical to the single-machine build — the paper's
exactness guarantee, on a mesh over the devices this process sees.

  python examples/distributed_forest.py      # the chips present (TPU)
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/distributed_forest.py  # a 2x4 mesh of host devices
"""
import os

import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import distributed, tree as tree_lib
from repro.core.forest import RandomForest
from repro.data.synthetic import make_tabular
from repro.launch.mesh import make_host_mesh


def main() -> None:
    mesh = make_host_mesh()
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.devices.size} devices)")

    ds = make_tabular("majority", 4000, num_informative=6, num_useless=2,
                      seed=3)
    params = tree_lib.TreeParams(max_depth=6, min_records=2)

    local = RandomForest(params, num_trees=3, seed=7).fit(ds)
    sup = distributed.make_2d_sharded_supersplit(mesh)
    dist = RandomForest(params, num_trees=3, seed=7).fit(ds, supersplit_fn=sup)

    for i, (a, b) in enumerate(zip(local.trees, dist.trees)):
        same = (a.num_nodes == b.num_nodes
                and (a.feature == b.feature).all()
                and np.allclose(a.threshold, b.threshold, atol=1e-4))
        print(f"tree {i}: local={a.num_nodes} nodes, "
              f"distributed={b.num_nodes} nodes, identical={same}")
        assert same, "distributed training must be EXACT (paper's guarantee)"

    print(f"distributed AUC: {dist.auc(ds):.4f} "
          f"(== local {local.auc(ds):.4f})")
    print("exact distributed training verified ✓")


if __name__ == "__main__":
    main()
