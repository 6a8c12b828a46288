"""Distributed DRF engine tests — run in a subprocess with 8 forced host
devices so the main pytest process keeps its single real device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_supersplits_exact():
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import splits, distributed
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(0)
        n, m, L, C = 512, 8, 3, 2
        num = rng.normal(size=(n, m)).astype(np.float32)
        y = rng.integers(0, C, n).astype(np.int32)
        w = rng.integers(0, 3, n).astype(np.float32)
        leaf = rng.integers(0, L + 1, n).astype(np.int32)
        si = np.argsort(num.T, axis=-1, kind='stable').astype(np.int32)
        sv = np.take_along_axis(num.T, si, -1)
        stats = splits.row_stats(jnp.asarray(y), jnp.asarray(w), C,
                                 'classification')
        cand = np.ones((m, L + 1), bool); cand[:, 0] = False
        ref_g, ref_t = jax.vmap(
            lambda v, s, c: splits.best_numeric_split_segment(
                v, jnp.asarray(leaf)[s], jnp.asarray(w)[s], stats[s], c, L)
        )(jnp.asarray(sv), jnp.asarray(si), jnp.asarray(cand))
        for maker in (distributed.make_column_sharded_supersplit,
                      distributed.make_2d_sharded_supersplit):
            fn = maker(mesh)
            g, t = fn(jnp.asarray(sv), jnp.asarray(si), jnp.asarray(leaf),
                      jnp.asarray(w), stats, jnp.asarray(cand), L,
                      'gini', 'classification', 1.0)
            fin = np.isfinite(np.asarray(ref_g))
            assert (np.isfinite(np.asarray(g)) == fin).all()
            np.testing.assert_allclose(np.asarray(g)[fin],
                                       np.asarray(ref_g)[fin], atol=1e-3)
            np.testing.assert_allclose(np.asarray(t)[fin],
                                       np.asarray(ref_t)[fin], atol=1e-4)
        print('SHARDED-EXACT-OK')
    """))


@pytest.mark.slow
def test_distributed_forest_equals_local():
    """Full tree built with the 2-D sharded supersplit == local tree."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed, tree as tree_lib
        from repro.core.dataset import from_numpy
        from repro.core.forest import RandomForest
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(1)
        n = 1024
        num = rng.normal(size=(n, 8)).astype(np.float32)
        y = ((num[:, 0] + num[:, 1] * num[:, 2]) > 0).astype(np.int32)
        ds = from_numpy(num, None, y)
        p = tree_lib.TreeParams(max_depth=4, leaf_pad=8)
        local = RandomForest(p, num_trees=2, seed=11).fit(ds)
        fn = distributed.make_2d_sharded_supersplit(mesh)
        dist = RandomForest(p, num_trees=2, seed=11).fit(ds, supersplit_fn=fn)
        for ta, tb in zip(local.trees, dist.trees):
            assert ta.num_nodes == tb.num_nodes
            np.testing.assert_array_equal(ta.feature, tb.feature)
            np.testing.assert_allclose(ta.threshold, tb.threshold, atol=1e-4)
        print('DIST-FOREST-OK')
    """))


@pytest.mark.slow
def test_hist_sharded_supersplit_psum_merge():
    """Histogram (PLANET-style) supersplit on the 2x4 mesh: per-shard
    (bins × stats) tables merged by ONE psum over the data axis must give
    the same forest as the local hist search — the network-complexity
    contrast baseline to the exact all_gather (DESIGN.md §6)."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed, tree as tree_lib
        from repro.core.dataset import from_numpy
        from repro.core.forest import RandomForest
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(1)
        n = 1024
        num = rng.normal(size=(n, 8)).astype(np.float32)
        y = ((num[:, 0] + num[:, 1] * num[:, 2]) > 0).astype(np.int32)
        ds = from_numpy(num, None, y)
        B = 32
        p = tree_lib.TreeParams(max_depth=4, leaf_pad=8, split_mode='hist',
                                num_bins=B)
        local = RandomForest(p, num_trees=2, seed=11).fit(ds)
        fn = distributed.make_hist_sharded_supersplit(mesh)
        dist = RandomForest(p, num_trees=2, seed=11).fit(ds, supersplit_fn=fn)
        for ta, tb in zip(local.trees, dist.trees):
            assert ta.num_nodes == tb.num_nodes
            np.testing.assert_array_equal(ta.feature, tb.feature)
            np.testing.assert_array_equal(ta.threshold, tb.threshold)
        print('HIST-PSUM-OK')
    """))


@pytest.mark.slow
def test_sharded_batched_forest_exact_and_hist():
    """The tentpole contract (ISSUE 4): sharded exact AND hist training run
    through the BATCHED build_forest path (tree_batch > 1) on the 2x4 mesh,
    produce trees bit-identical to the local batched builder, and issue D
    (one per depth) — not T·D — level programs for the whole batch."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import obs
        from repro.core import distributed, tree as tree_lib
        from repro.core.dataset import from_numpy
        from repro.core.forest import RandomForest
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(1)
        n = 1024
        num = rng.normal(size=(n, 8)).astype(np.float32)
        y = ((num[:, 0] + num[:, 1] * num[:, 2]) > 0).astype(np.int32)
        ds = from_numpy(num, None, y)
        configs = [
            (tree_lib.TreeParams(max_depth=4, leaf_pad=8),
             distributed.make_2d_sharded_supersplit(mesh)),
            (tree_lib.TreeParams(max_depth=4, leaf_pad=8, split_mode='hist',
                                 num_bins=32),
             distributed.make_hist_sharded_supersplit(mesh)),
        ]
        for p, eng in configs:
            local = RandomForest(p, num_trees=4, seed=11, tree_batch=4).fit(ds)
            c0 = obs.counter("level.dispatches")
            s0 = obs.counter("level.tree_dispatches")
            dist = RandomForest(p, num_trees=4, seed=11,
                                tree_batch=4).fit(ds, engine=eng)
            D = max(t.max_depth_reached for t in dist.trees)
            programs = obs.counter("level.dispatches") - c0
            assert D <= programs <= p.max_depth + 1, (programs, D)
            assert obs.counter("level.tree_dispatches") == s0  # no per-tree
            for ta, tb in zip(local.trees, dist.trees):
                assert ta.num_nodes == tb.num_nodes
                np.testing.assert_array_equal(ta.feature, tb.feature)
                np.testing.assert_array_equal(ta.threshold, tb.threshold)
                np.testing.assert_array_equal(ta.value, tb.value)
        print('SHARDED-BATCHED-OK')
    """))


def test_sharded_hist_class_path_equals_one_device():
    """The sharded hist engine on a 2x2 mesh of forced CPU devices
    scatters at the rows' class ids in every table build (in-memory fit
    with subtraction, and streamed chunks) and trains the one-device
    trees node for node."""
    print(_run("""
        import numpy as np
        from repro.core import splits, tree as tree_lib
        from repro.core.dataset import ArrayRowSource
        from repro.core.forest import RandomForest
        from repro.core.level.sharded import ShardedHistNumeric
        from repro.data.synthetic import make_tabular
        from repro.launch.mesh import make_host_mesh
        seen = []
        build = splits.feature_count_tables
        def spy(*args, labels=None, **kw):
            seen.append(labels is not None)
            return build(*args, labels=labels, **kw)
        ds = make_tabular('xor', n=640, num_informative=4, num_useless=4,
                          seed=2)
        p = tree_lib.TreeParams(max_depth=5, split_mode='hist', num_bins=16)
        local = RandomForest(p, num_trees=2, seed=3).fit(ds)
        splits.feature_count_tables = spy
        eng = ShardedHistNumeric(mesh=make_host_mesh(2, 2))
        dist = RandomForest(p, num_trees=2, seed=3).fit(ds, engine=eng)
        in_memory = len(seen)
        src = ArrayRowSource.from_dataset(ds, p.num_bins, chunk_size=200)
        streamed = RandomForest(p, num_trees=2, seed=3).fit_streamed(
            src, engine=eng)
        assert in_memory and len(seen) > in_memory and all(seen), seen
        def fingerprint(rf):
            return [(t.num_nodes, t.feature.tolist(), t.threshold.tolist(),
                     t.children.tolist(), t.value.tolist(),
                     t.n_node.tolist()) for t in rf.trees]
        assert fingerprint(dist) == fingerprint(local)
        assert fingerprint(streamed) == fingerprint(local)
        print('SHARDED-HIST-CLASS-PATH-OK')
    """, devices=4))


@pytest.mark.slow
def test_sharded_hist_subtraction_bit_identical():
    """ISSUE 5 tentpole on the 2x4 mesh: ShardedHistNumeric with histogram
    subtraction (packed build-slot tables psum'd, siblings derived as
    parent − sibling) must equal BOTH its own plain rebuild and the local
    builder node-for-node, batched and per-tree, with prune_closed_frac
    on — pruning renumbers rows, not leaves, so the carried tables
    survive row compaction under the mesh too."""
    print(_run("""
        import dataclasses
        import numpy as np
        from repro.core import distributed, tree as tree_lib
        from repro.core.dataset import from_numpy
        from repro.core.forest import RandomForest
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(5)
        n = 2048
        num = rng.normal(size=(n, 8)).astype(np.float32)
        y = ((num[:, 0] > 0.8) | (num[:, 1] * num[:, 2] > 1.0)).astype(np.int32)
        ds = from_numpy(num, None, y)
        p = tree_lib.TreeParams(max_depth=6, min_records=20, leaf_pad=8,
                                split_mode='hist', num_bins=32,
                                prune_closed_frac=0.3)
        eng = distributed.make_hist_sharded_supersplit(mesh)
        def fingerprint(rf):
            return [(t.num_nodes, t.feature.tolist(), t.threshold.tolist(),
                     t.value.tolist()) for t in rf.trees]
        local = RandomForest(p, num_trees=4, seed=11, tree_batch=4).fit(ds)
        for tb in (4, 1):
            sub = RandomForest(p, num_trees=4, seed=11,
                               tree_batch=tb).fit(ds, engine=eng)
            plain = RandomForest(
                dataclasses.replace(p, hist_subtract=False), num_trees=4,
                seed=11, tree_batch=tb).fit(ds, engine=eng)
            assert fingerprint(sub) == fingerprint(plain), tb
            assert fingerprint(sub) == fingerprint(local), tb
        print('SHARDED-HIST-SUBTRACT-OK')
    """))


@pytest.mark.slow
def test_sharded_pruning_through_batched_builder():
    """prune_closed_frac under the mesh: the batched driver drops only
    common-closed rows rounded to the row-shard width, so shard_map
    divisibility holds and the forest stays bit-identical."""
    print(_run("""
        import numpy as np
        from repro.core import distributed, tree as tree_lib
        from repro.core.dataset import from_numpy
        from repro.core.forest import RandomForest
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(0)
        n = 2000
        num = rng.normal(size=(n, 8)).astype(np.float32)
        y = (num[:, 0] > 1.2).astype(np.int32)   # leaves close early
        ds = from_numpy(num, None, y)
        base_p = tree_lib.TreeParams(max_depth=8, min_records=50)
        base = RandomForest(base_p, num_trees=3, seed=3, tree_batch=3).fit(ds)
        import dataclasses
        pp = dataclasses.replace(base_p, prune_closed_frac=0.3)
        dist = RandomForest(pp, num_trees=3, seed=3, tree_batch=3).fit(
            ds, engine=distributed.make_2d_sharded_supersplit(mesh))
        for ta, tb in zip(base.trees, dist.trees):
            assert ta.num_nodes == tb.num_nodes
            np.testing.assert_array_equal(ta.feature, tb.feature)
            np.testing.assert_array_equal(ta.threshold, tb.threshold)
        print('SHARDED-PRUNE-OK')
    """))


@pytest.mark.slow
def test_sharded_categorical_engine():
    """The categorical table engine under the mesh (psum of the per-shard
    (leaf, category, stat) tables) equals the local table search."""
    print(_run("""
        import numpy as np
        from repro.core import distributed, tree as tree_lib
        from repro.core.dataset import from_numpy
        from repro.core.forest import RandomForest
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(1)
        n = 1024
        num = rng.normal(size=(n, 8)).astype(np.float32)
        cat = rng.integers(0, 5, size=(n, 4)).astype(np.int32)
        y = ((num[:, 0] > 0) ^ (cat[:, 0] >= 3)).astype(np.int32)
        ds = from_numpy(num, cat, y)
        p = tree_lib.TreeParams(max_depth=4)
        local = RandomForest(p, num_trees=3, seed=7, tree_batch=3).fit(ds)
        dist = RandomForest(p, num_trees=3, seed=7, tree_batch=3).fit(
            ds, engine=distributed.make_2d_sharded_supersplit(mesh),
            cat_engine=distributed.make_categorical_sharded_supersplit(mesh))
        for ta, tb in zip(local.trees, dist.trees):
            np.testing.assert_array_equal(ta.feature, tb.feature)
            np.testing.assert_array_equal(ta.threshold, tb.threshold)
            np.testing.assert_array_equal(ta.cat_mask, tb.cat_mask)
        print('SHARDED-CAT-OK')
    """))


@pytest.mark.slow
def test_sharded_bit_broadcast():
    """1-bit condition evaluation via psum over the splitter axis (Alg.2
    step 5/7) matches local evaluation."""
    print(_run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import distributed
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        rng = np.random.default_rng(0)
        n, m, L = 256, 8, 3
        num = rng.normal(size=(n, m)).astype(np.float32)
        leaf = rng.integers(0, L + 1, n).astype(np.int32)
        feat = rng.integers(0, m, L + 1).astype(np.int32)
        thr = rng.normal(size=L + 1).astype(np.float32)
        fn = distributed.make_sharded_evaluate(mesh)
        bits = fn(jnp.asarray(num.T), jnp.asarray(leaf), jnp.asarray(feat),
                  jnp.asarray(thr), m)
        expect = num[np.arange(n), feat[leaf]] <= thr[leaf]
        np.testing.assert_array_equal(np.asarray(bits), expect)
        print('BIT-BROADCAST-OK')
    """))
