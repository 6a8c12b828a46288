"""Streaming-parity suite: out-of-core training is BIT-EXACT.

`RandomForest.fit_streamed(source)` must produce node-for-node identical
trees to `fit(ds)` in hist mode — same features, same decoded float
thresholds, same child numbering, same leaf values and counts — for every
chunk size (including a single padded chunk larger than n and chunk=1),
for batched and per-tree building, with Sprint pruning on, and from a
disk-backed memory-mapped bin cache.  The chain that makes this possible
(DESIGN.md §8): streaming quantile edges bit-equal to the in-memory
recipe -> identical bin ids -> order-independent integer table
accumulation -> identical scoring arithmetic -> identical host decisions.

Also here: the chunked-accumulation property test (random chunk
boundaries vs one-pass tables, exact equality), the trace-count guard
(one compiled chunk program per level shape — no retrace per chunk), and
the 2x4-mesh sharded streaming parity subprocess test.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import presort, splits, tree as tree_lib
from repro.core.dataset import (ArrayRowSource, MemmapRowSource, RowSource,
                                from_numpy)
from repro.core.forest import RandomForest
from repro.data.synthetic import make_tabular
from repro.kernels import ops as kops

try:
    from hypothesis import given, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # optional dev dependency
    HAVE_HYPOTHESIS = False

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

FIELDS = ("feature", "children", "threshold", "is_cat", "cat_mask",
          "value", "n_node", "gain", "depth")


def _assert_identical(ta, tb, ctx=""):
    """Node-for-node bitwise equality of two flat trees."""
    assert ta.num_nodes == tb.num_nodes, f"{ctx}: node count"
    for f in FIELDS:
        np.testing.assert_array_equal(
            getattr(ta, f), getattr(tb, f), err_msg=f"{ctx}: {f}")


def _assert_forests_identical(fa, fb, ctx=""):
    assert len(fa.trees) == len(fb.trees), ctx
    for t, (ta, tb) in enumerate(zip(fa.trees, fb.trees)):
        _assert_identical(ta, tb, f"{ctx}/tree{t}")


@pytest.fixture(scope="module")
def hist_setup():
    """A reference in-memory hist fit plus its streamable source."""
    ds = make_tabular("xor", n=900, num_informative=4, num_useless=2,
                      seed=3)
    params = tree_lib.TreeParams(max_depth=6, split_mode="hist",
                                 num_bins=32)
    ref = RandomForest(params=params, num_trees=3, seed=7).fit(ds)
    return ds, params, ref


# ---------------------------------------------------------------------------
# Core parity: chunk sizes, batching, pruning, disk backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [900, 300, 977, 173])
def test_streamed_fit_bit_identical_across_chunk_sizes(hist_setup, chunk):
    """chunk == n (one block), n/3 (even), 977 > n (single padded block),
    173 (uneven tail) — all bit-identical to the in-memory fit."""
    ds, params, ref = hist_setup
    src = ArrayRowSource.from_dataset(ds, params.num_bins, chunk_size=chunk)
    fs = RandomForest(params=params, num_trees=3, seed=7).fit_streamed(src)
    _assert_forests_identical(ref, fs, f"chunk{chunk}")


def test_streamed_fit_chunk_one_per_tree():
    """chunk_size=1 (every row its own block) through the per-tree builder
    (tree_batch=1) — the degenerate extreme of the accumulation loop."""
    ds = make_tabular("xor", n=96, num_informative=3, num_useless=1, seed=5)
    params = tree_lib.TreeParams(max_depth=4, split_mode="hist",
                                 num_bins=16)
    ref = RandomForest(params=params, num_trees=2, seed=2,
                       tree_batch=1).fit(ds)
    src = ArrayRowSource.from_dataset(ds, params.num_bins, chunk_size=1)
    fs = RandomForest(params=params, num_trees=2, seed=2,
                      tree_batch=1).fit_streamed(src)
    _assert_forests_identical(ref, fs, "chunk1")


def test_streamed_fit_with_pruning():
    """Sprint record pruning compacts the HOST row state mid-training; the
    trees must not notice."""
    ds = make_tabular("majority", n=600, num_informative=4, num_useless=2,
                      seed=1)
    params = tree_lib.TreeParams(max_depth=5, split_mode="hist",
                                 num_bins=16, prune_closed_frac=0.25)
    ref = RandomForest(params=params, num_trees=3, seed=9).fit(ds)
    src = ArrayRowSource.from_dataset(ds, params.num_bins, chunk_size=97)
    fs = RandomForest(params=params, num_trees=3, seed=9).fit_streamed(src)
    _assert_forests_identical(ref, fs, "pruned")


def test_class_path_engages_in_fit_and_fit_streamed(monkeypatch):
    """Every table build of a classification fit, in memory and streamed,
    scatters at the rows' class ids (`labels=`), and the two fits still
    give the same trees node for node.  The shapes are this test's own,
    so the level and chunk programs trace afresh under the spy."""
    seen = []
    build = splits.feature_count_tables

    def spy(*args, labels=None, **kw):
        seen.append(labels is not None)
        return build(*args, labels=labels, **kw)
    monkeypatch.setattr(splits, "feature_count_tables", spy)
    ds = make_tabular("xor", n=731, num_informative=4, num_useless=3,
                      seed=8)
    params = tree_lib.TreeParams(max_depth=5, split_mode="hist",
                                 num_bins=24)
    ref = RandomForest(params=params, num_trees=2, seed=5).fit(ds)
    in_memory = len(seen)
    src = ArrayRowSource.from_dataset(ds, params.num_bins, chunk_size=250)
    fs = RandomForest(params=params, num_trees=2, seed=5).fit_streamed(src)
    assert in_memory and len(seen) > in_memory and all(seen), seen
    _assert_forests_identical(ref, fs, "class-path")


def test_memmap_source_parity(hist_setup, tmp_path):
    """Disk-backed bin cache (built by the streaming quantizer, no full
    float column ever materialized) trains the same trees, and its edges
    are bit-equal to the in-memory quantization."""
    ds, params, ref = hist_setup
    mem = ArrayRowSource.from_dataset(ds, params.num_bins)
    src = MemmapRowSource.from_numpy(
        np.asarray(ds.num), np.asarray(ds.labels),
        num_bins=params.num_bins, path=str(tmp_path / "bins.npy"),
        chunk_size=97, num_classes=ds.num_classes)
    np.testing.assert_array_equal(src.edges, mem.edges)
    fs = RandomForest(params=params, num_trees=3, seed=7).fit_streamed(src)
    _assert_forests_identical(ref, fs, "memmap")
    # predictions follow from node-identity, but check the packed path too
    xq = np.asarray(ds.num[:64])
    xc = np.zeros((64, 0), np.int32)
    np.testing.assert_array_equal(np.asarray(ref.predict(xq, xc)),
                                  np.asarray(fs.predict(xq, xc)))


def test_streaming_quantile_edges_bit_equal():
    """The 3-pass radix-select quantizer == sort-the-column quantization,
    bit for bit, across distributions and bucket budgets."""
    cases = [(1000, 3, 16, "normal"), (977, 2, 255, "uniform"),
             (64, 4, 64, "ties"), (5000, 1, 7, "negskew")]
    for n, m, B, kind in cases:
        rng = np.random.default_rng(hash(kind) % 2**31)
        if kind == "normal":
            num = rng.normal(size=(n, m))
        elif kind == "uniform":
            num = rng.uniform(-5, 5, size=(n, m))
        elif kind == "ties":
            num = np.round(rng.normal(size=(n, m)) * 2) / 2
        else:
            num = -np.abs(rng.normal(size=(n, m))) ** 3
        num = num.astype(np.float32)

        def chunks(num=num):
            for lo in range(0, n, 173):
                yield num[lo:lo + 173]

        got = presort.streaming_quantile_edges(chunks, n, m, B)
        si = presort.presort_columns(jnp.asarray(num))
        sv = presort.gather_sorted(jnp.asarray(num), si)
        want = np.asarray(presort.quantize_edges(sv, B))
        np.testing.assert_array_equal(got, want, err_msg=f"{kind}/B{B}")
        np.testing.assert_array_equal(
            presort.bin_block(num, got),
            np.asarray(presort.bin_columns(jnp.asarray(num),
                                           jnp.asarray(want))),
            err_msg=f"{kind}/B{B}/bins")


# ---------------------------------------------------------------------------
# Error paths + from_numpy laziness
# ---------------------------------------------------------------------------

def test_stream_error_paths(hist_setup):
    ds, params, _ = hist_setup
    src = ArrayRowSource.from_dataset(ds, params.num_bins)
    exact = tree_lib.TreeParams(max_depth=3, split_mode="exact")
    with pytest.raises(ValueError, match="only hist streams"):
        RandomForest(params=exact, num_trees=1).fit_streamed(src)
    with pytest.raises(TypeError, match="fit_streamed"):
        RandomForest(params=params, num_trees=1).fit(src)
    with pytest.raises(TypeError, match="RowSource"):
        RandomForest(params=params, num_trees=1).fit_streamed(ds)
    bad = tree_lib.TreeParams(max_depth=3, split_mode="hist", num_bins=64)
    with pytest.raises(ValueError, match="num_bins"):
        RandomForest(params=bad, num_trees=1).fit_streamed(src)


def test_from_numpy_stays_host_resident():
    """`from_numpy` must NOT device-put columns eagerly — a memmap input
    would fault the whole file.  The fit entry points device-put later."""
    num = np.random.default_rng(0).normal(size=(50, 3)).astype(np.float32)
    y = (num[:, 0] > 0).astype(np.int32)
    ds = from_numpy(num, None, y)
    assert isinstance(ds.num, np.ndarray)
    assert isinstance(ds.labels, np.ndarray)
    # ...and training still works from the lazy dataset
    params = tree_lib.TreeParams(max_depth=2, split_mode="hist", num_bins=8)
    f = RandomForest(params=params, num_trees=1, seed=0).fit(ds)
    assert f.trees[0].num_nodes >= 1


# ---------------------------------------------------------------------------
# Trace counts: one compiled program per depth, not per chunk
# ---------------------------------------------------------------------------

def test_streaming_one_program_per_level_shape(hist_setup):
    """Chunk-program compilations are bounded by the number of distinct
    (level shape) configurations — O(log L), never O(chunks) — and a warm
    refit with identical shapes adds chunk CALLS but ZERO new traces."""
    ds, params, _ = hist_setup
    src = ArrayRowSource.from_dataset(ds, params.num_bins, chunk_size=123)

    c0 = obs.counter("stream.chunk_dispatches")
    t0 = obs.counter("stream.traces")
    s0 = obs.counter("stream.score_traces")
    RandomForest(params=params, num_trees=3, seed=7).fit_streamed(src)
    calls = obs.counter("stream.chunk_dispatches") - c0
    traces = obs.counter("stream.traces") - t0
    straces = obs.counter("stream.score_traces") - s0
    chunks_per_level = -(-900 // 123)
    assert calls >= chunks_per_level          # it really streamed
    # statics are (plan, Lp, Lpp, root, need_tables): at most one trace per
    # (depth-padded leaf count transition) + the root level — far fewer
    # than the number of chunk dispatches
    assert traces <= params.max_depth + 2, (traces, calls)
    assert traces < calls
    assert straces <= params.max_depth + 1

    # warm refit: same shapes -> zero new compilations, calls still grow
    t1 = obs.counter("stream.traces")
    s1 = obs.counter("stream.score_traces")
    c1 = obs.counter("stream.chunk_dispatches")
    RandomForest(params=params, num_trees=3, seed=7).fit_streamed(src)
    assert obs.counter("stream.traces") == t1
    assert obs.counter("stream.score_traces") == s1
    assert obs.counter("stream.chunk_dispatches") > c1


# ---------------------------------------------------------------------------
# Chunked-accumulation property: random boundaries == one pass, exactly
# ---------------------------------------------------------------------------

def _acc_case(seed, n=257, m=3, L=4, B=16, C=3):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, size=(m, n)).astype(np.uint8)
    leaf = rng.integers(0, L + 1, n).astype(np.int32)
    w = rng.integers(0, 3, n).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    return bins, leaf, w, y


def _check_chunked_accumulation(seed, cuts):
    """Tables accumulated over arbitrary chunk boundaries (uneven, empty,
    single-row) must equal the single-pass tables EXACTLY, for both the
    jnp segment-sum path and the Pallas kernel path."""
    n, m, L, B, C = 257, 3, 4, 16, 3
    bins, leaf, w, y = _acc_case(seed, n, m, L, B, C)
    stats = splits.row_stats(jnp.asarray(y), jnp.asarray(w), C,
                             "classification")
    one_pass = np.asarray(splits.feature_count_tables(
        jnp.asarray(bins), jnp.asarray(leaf), jnp.asarray(w), stats, L, B))
    bounds = [0] + sorted(min(c, n) for c in cuts) + [n]
    acc = np.zeros_like(one_pass)
    kacc = np.zeros_like(one_pass)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi == lo:                      # empty chunk: must be a no-op
            continue
        sl = slice(lo, hi)
        acc += np.asarray(splits.feature_count_tables(
            jnp.asarray(bins[:, sl]), jnp.asarray(leaf[sl]),
            jnp.asarray(w[sl]), stats[lo:hi], L, B))
        kacc += np.asarray(kops.feature_tables(
            jnp.asarray(bins[:, sl]), jnp.asarray(leaf[sl]),
            jnp.asarray(w[sl]), jnp.asarray(y[sl]), B=B, W=L + 1,
            num_classes=C))
    np.testing.assert_array_equal(acc, one_pass, err_msg=f"seed{seed}")
    np.testing.assert_array_equal(kacc, one_pass, err_msg=f"seed{seed}/k")


@pytest.mark.parametrize("seed,cuts", [
    (0, [100, 200]),                       # even-ish
    (1, [1, 2, 250]),                      # single-row chunks + long tail
    (2, [50, 50, 128]),                    # empty chunk in the middle
    (3, []),                               # one chunk == one pass
])
def test_chunked_table_accumulation_exact(seed, cuts):
    _check_chunked_accumulation(seed, cuts)


if HAVE_HYPOTHESIS:

    @pytest.mark.hypothesis
    @given(st.integers(0, 10_000),
           st.lists(st.integers(0, 257), max_size=8))
    def test_property_chunked_accumulation(seed, cuts):
        _check_chunked_accumulation(seed, cuts)


# ---------------------------------------------------------------------------
# Sharded streaming parity (2x4 mesh, subprocess — pattern from
# tests/test_distributed.py)
# ---------------------------------------------------------------------------

def _run(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_streaming_parity():
    """ShardedHistNumeric streaming (collective-free per-chunk shard_map
    accumulation, ONE psum per level) is bit-identical to the local
    engine's streamed fit AND to the in-memory sharded fit."""
    out = _run("""
        import numpy as np
        from repro.core import tree as tree_lib
        from repro.core.dataset import ArrayRowSource
        from repro.core.forest import RandomForest
        from repro.core.level.sharded import ShardedHistNumeric
        from repro.data.synthetic import make_tabular
        from repro.launch.mesh import make_host_mesh

        ds = make_tabular('xor', n=912, num_informative=5, num_useless=3,
                          seed=4)
        params = tree_lib.TreeParams(max_depth=5, split_mode='hist',
                                     num_bins=16, prune_closed_frac=0.5)
        eng = ShardedHistNumeric(mesh=make_host_mesh(2, 4))
        ref = RandomForest(params=params, num_trees=3, seed=7).fit(
            ds, engine=eng)
        src = ArrayRowSource.from_dataset(ds, params.num_bins,
                                          chunk_size=301)
        fs = RandomForest(params=params, num_trees=3, seed=7).fit_streamed(
            src, engine=eng)
        fl = RandomForest(params=params, num_trees=3, seed=7).fit_streamed(
            src)
        # fault-tolerance under the mesh engine (DESIGN.md §9): interrupt
        # a checkpointed sharded streamed fit with a persistent read
        # fault, resume from the snapshot, and land bit-identical
        import tempfile
        from repro.core.dataset import StreamReadError
        from repro.testing.faults import FaultyRowSource
        with tempfile.TemporaryDirectory() as ckdir:
            dead = FaultyRowSource(src, persistent={9})
            try:
                RandomForest(params=params, num_trees=3,
                             seed=7).fit_streamed(dead, engine=eng,
                                                  checkpoint_dir=ckdir)
                raise SystemExit('expected StreamReadError')
            except StreamReadError:
                pass
            fr = RandomForest(params=params, num_trees=3,
                              seed=7).fit_streamed(src, engine=eng,
                                                   checkpoint_dir=ckdir,
                                                   resume=True)
        for a, b in ((ref, fs), (fl, fs), (fr, fs)):
            for ta, tb in zip(a.trees, b.trees):
                assert ta.num_nodes == tb.num_nodes
                for f in ('feature', 'children', 'threshold', 'value',
                          'n_node', 'gain', 'depth'):
                    np.testing.assert_array_equal(getattr(ta, f),
                                                  getattr(tb, f), err_msg=f)
        print('SHARDED-STREAM-OK')
    """)
    assert "SHARDED-STREAM-OK" in out
