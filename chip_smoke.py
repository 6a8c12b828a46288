#!/usr/bin/env python3
"""Bring-up smoke of the forest trainer and server on a TPU.

Drives the main path once through the entry points a user calls, on a
HIGGS-shaped dataset (UCI HIGGS, Baldi et al. 2014: 28 numeric columns,
binary, nearly balanced) and a mixed set with categorical columns of
arity 1000-1024, both generated from --seed.  The fits grow trees of
depth 12 on the chip (4 trees; 1 in (c)), one phase after another:

  parity  at n = 2^16, node for node: on the mixed set, exact `scan` ==
          `kernel` == `segment` and `segment` == `core/reference.py`; on
          the HIGGS-like set, hist (255 bins) `kernel` == `segment` and
          `fit_streamed` == `fit`;
  (b)     `fit` in `split_mode="hist"` at n = 2^22, `segment` and `kernel`;
  (d)     `PackedForest.save` -> `ForestServer.load` -> single-row and
          batched requests, each answer == `rf.predict_proba`;
  (c)     `fit_streamed` of one tree from a `MemmapRowSource` built on
          local disk at n = 2^23, with level checkpoints;
  (a)     exact `RandomForest.fit` at n = 2^18, `segment` and `kernel`.

Every full-size forest must clear AUC_FLOOR on held-out rows; whether the
full-size `kernel` and `segment` forests agree node for node is printed
as an observation (the parity phase is the check).

The sizes are cut so that a cold run ends well inside 1200 s on one v5e.
There (smoke timings, cold cache) the parity phase took 345 s and the two
hist fits at 2^22 took 281 s, 100 s of it compiling.  A streamed level
scatters every row (no histogram subtraction), so four streamed trees at
2^23 would take about four times the in-memory `segment` fit: (c) grows
one tree.  Exact mode runs at 2^18: its `segment` level program alone
compiles for 50-70 s at 2^20 and needs more than a v5e's 16 GB of HBM
for one tree at 2^22.

Compilation takes most of a cold run: one level program takes 10-60 s to
compile for a v5e.  So every fit pads its open-leaf count to WIDE_PAD
(`TreeParams.leaf_pad`), the widest level of a depth-12 tree, and compiles
one level program (two with histogram subtraction) instead of one per
depth; trees do not depend on the padding.  The full-size `kernel` fits
pad to KERNEL_PAD instead, since the Pallas kernels loop over leaf blocks
and their work grows with the padded width.  The full-size `segment` fits
build one tree per level program (`tree_batch=1`): the four-tree exact
fit at 2^18 spent 150 s compiling on a v5e, and its two level programs
compile for 171 s for a described v5e against 54 s for one tree.

    python chip_smoke.py              # one chip: the phases above
    python chip_smoke.py --chips 4    # only the sharded engines on a
                                      # data x model mesh of four chips,
                                      # each fit == the same fit on one

It exits non-zero, printing no result, when JAX finds no TPU: it never
runs on the CPU.  Any failed phase or check exits non-zero.  The timings
printed on the way are smoke timings, not benchmark metrics.  The last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_PARITY = 1 << 16
N_EXACT = 1 << 18
N_HIST = 1 << 22
N_STREAM = 1 << 23
N_TEST = 1 << 18
CHUNK = 1 << 20         # rows per streamed chunk
NUM_TREES = 4
STREAM_TREES = 1
DEPTH = 12
WIDE_PAD = 1 << (DEPTH - 1)
KERNEL_PAD = 256
AUC_FLOOR = 0.70        # held-out AUC of every full-size fit (binary)
N_SHARDED = 1 << 16     # --chips 4
SHARDED_DEPTH = 5


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Seconds JAX spent compiling (or loading from the persistent cache)
    and the cache hits, from `jax.monitoring` events."""

    def __init__(self):
        self.secs, self.programs, self.hits = 0.0, 0, 0

    def duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.programs += 1

    def event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.duration)
        jax.monitoring.register_event_listener(self.event)
        return self


COMPILES = CompileLog()


def timed(name: str, fn):
    t0 = time.perf_counter()
    c0, p0, h0 = COMPILES.secs, COMPILES.programs, COMPILES.hits
    out = fn()
    log(f"[smoke timing] {name}: {time.perf_counter() - t0:.3f} s, of which "
        f"compile or cache load {COMPILES.secs - c0:.3f} s "
        f"({COMPILES.programs - p0} programs, {COMPILES.hits - h0} cache hits)")
    return out


# ---------------------------------------------------------------------------
# Data (numpy, from the seed)
# ---------------------------------------------------------------------------

def higgs_like(n: int, seed: int):
    """(num (n, 28) f32, y (n,) int32) shaped like UCI HIGGS.

    21 low-level kinematic columns (lepton pT/eta/phi, missing-energy
    magnitude/phi, four jets' pT/eta/phi/b-tag; the b-tags take 3 values,
    so exact search meets heavy ties) and 7 high-level invariant-mass-like
    columns derived from them.  About 53% of rows are signal; signal
    shifts the jet energy scale and b-tag rates and adds a resonance to
    two of the masses, so a forest separates the classes partly.
    """
    import numpy as np
    rng = np.random.default_rng(seed)
    f32 = np.float32
    y = (rng.random(n) < 0.53).astype(np.int32)
    sig = y.astype(f32)

    def pt(scale):
        return (np.exp(0.6 * rng.standard_normal(n, f32)) * scale).astype(f32)

    def eta():
        return (1.2 * rng.standard_normal(n, f32)).astype(f32)

    def phi():
        return rng.uniform(-np.pi, np.pi, n).astype(f32)

    lep = [pt(1.0), eta(), phi()]
    met = [pt(0.9), phi()]
    jets = []
    for j in range(4):
        p = pt(1.0 - 0.15 * j) * (1.0 + 0.12 * sig)
        btag = rng.choice(np.array([0.0, 1.1, 2.2], f32), n,
                          p=[0.6, 0.2, 0.2])
        btag = np.where(sig.astype(bool) & (rng.random(n) < 0.25), f32(2.2),
                        btag)
        jets.append([p, eta(), phi(), btag])

    def mass(a, b):
        return np.sqrt(2 * a[0] * b[0] * np.maximum(
            np.cosh(a[1] - b[1]) - np.cos(a[2] - b[2]), 0)).astype(f32)

    res = np.where(sig > 0, 1.0 + 0.1 * rng.standard_normal(n, f32), 0.0)
    high = [mass(jets[0], jets[1]),
            mass(jets[0], jets[1]) + mass(jets[1], jets[2]),
            mass(lep, [met[0], np.zeros(n, f32), met[1]]),
            mass(jets[0], lep) + 0.5 * res,
            mass(jets[2], jets[3]) + res,
            mass(jets[1], jets[3]) + 0.5 * mass(lep, jets[2]),
            mass(jets[0], jets[2]) + mass(lep, jets[3])]
    cols = lep + met + [c for jet in jets for c in jet] + high
    return np.stack(cols, axis=1).astype(f32), y


def mixed(n: int, seed: int):
    """(num (n, 8) f32, cat (n, 4) int32, y, arities): 8 of the HIGGS-like
    columns plus 4 categorical columns of arity 1000-1024, two of them
    carrying signal through a per-category rate."""
    import numpy as np
    num, y = higgs_like(n, seed)
    num = num[:, [0, 3, 5, 9, 21, 24, 25, 27]]
    rng = np.random.default_rng(seed + 1)
    arities = (1000, 1000, 1024, 1024)
    cat = np.stack([rng.integers(0, a, n) for a in arities], 1).astype(
        np.int32)
    for j in (0, 2):
        rate = rng.random(arities[j]) < 0.5
        flip = rate[cat[:, j]] & (rng.random(n) < 0.3)
        y = np.where(flip, 1 - y, y).astype(np.int32)
    return num, cat, y, arities


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


TREE_FIELDS = ("feature", "children", "threshold", "is_cat", "cat_mask",
               "value", "n_node", "gain", "depth")


def identical(ta, tb) -> bool:
    """Node-for-node equality of two trees (the parity suites' rule)."""
    import numpy as np
    return ta.num_nodes == tb.num_nodes and all(
        np.array_equal(getattr(ta, f), getattr(tb, f)) for f in TREE_FIELDS)


def same_trees(a, b, ctx: str) -> None:
    import numpy as np
    check(len(a) == len(b), f"{ctx}: {len(a)} vs {len(b)} trees")
    for t, (ta, tb) in enumerate(zip(a, b)):
        check(ta.num_nodes == tb.num_nodes,
              f"{ctx}/tree{t}: {ta.num_nodes} vs {tb.num_nodes} nodes")
        for name in TREE_FIELDS:
            np.testing.assert_array_equal(getattr(ta, name), getattr(tb, name),
                                          err_msg=f"{ctx}/tree{t}:{name}")
    log(f"{ctx}: node for node equal ({len(a)} trees, depth "
        f"{max(t.max_depth_reached for t in a)})")


def agreement(a, b, ctx: str) -> None:
    """An observation, not a check: how many trees agree node for node."""
    same = sum(identical(ta, tb) for ta, tb in zip(a, b))
    log(f"{ctx}: {same} of {len(a)} trees node for node equal "
        f"(observation)")


def check_auc(rf, test, ctx: str) -> None:
    auc = rf.auc(test)
    log(f"{ctx}: held-out AUC {auc:.6f} (floor {AUC_FLOOR})")
    check(auc > AUC_FLOOR, f"{ctx}: AUC {auc} <= {AUC_FLOOR}")


def peak_memory(dev) -> None:
    stats = dev.memory_stats() or {}
    check("peak_bytes_in_use" in stats, f"no peak_bytes_in_use in {stats}")
    log(f"peak device memory: {stats['peak_bytes_in_use']} bytes")


def params(**kw):
    from repro.core.tree import TreeParams
    return TreeParams(**{"max_depth": DEPTH, "leaf_pad": WIDE_PAD, **kw})


HIST = dict(split_mode="hist", num_bins=255)


def forest(p, seed, num_trees=NUM_TREES, tree_batch=None):
    from repro.core.forest import RandomForest
    return RandomForest(params=p, num_trees=num_trees, seed=seed,
                        tree_batch=tree_batch)


def fit(name: str, p, ds, seed: int, tree_batch=None):
    return timed(name, lambda: forest(p, seed,
                                      tree_batch=tree_batch).fit(ds))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def on_device(ds):
    """The dataset with its columns on the device once, shared by every
    fit that reads it (a fit device-puts host columns itself)."""
    import dataclasses
    import jax.numpy as jnp
    return dataclasses.replace(ds, num=jnp.asarray(ds.num),
                               cat=jnp.asarray(ds.cat),
                               labels=jnp.asarray(ds.labels))


def reference_trees(ds, seed: int):
    """`core/reference.py`'s seed builder, tree by tree."""
    from repro.core import presort
    from repro.core.reference import build_tree_reference
    si = presort.presort_columns(ds.num)
    kw = dict(num=ds.num, cat=ds.cat, labels=ds.labels,
              sorted_vals=presort.gather_sorted(ds.num, si), sorted_idx=si,
              arities=ds.arities, num_classes=ds.num_classes)
    return [build_tree_reference(params=params(), seed=seed, tree_idx=t,
                                 **kw)[0] for t in range(NUM_TREES)]


def parity(seed: int, work: Path) -> None:
    """Node-for-node parity at N_PARITY: the exact engines and the seed
    reference on the mixed set, the hist engines and the streamed fit on
    the HIGGS-like set."""
    import numpy as np
    from repro.core.dataset import MemmapRowSource, from_numpy

    ds = on_device(from_numpy(*mixed(N_PARITY, seed)))
    got = {b: fit(f"parity mixed exact {b} n={N_PARITY}", params(backend=b),
                  ds, seed).trees for b in ("segment", "scan", "kernel")}
    ref = timed(f"parity mixed reference n={N_PARITY}",
                lambda: reference_trees(ds, seed))
    check(any(t.is_cat.any() for t in got["segment"]),
          "parity/mixed: no categorical split")
    for b in ("scan", "kernel"):
        same_trees(got[b], got["segment"], f"parity/mixed exact {b} == segment")
    same_trees(got["segment"], ref, "parity/mixed exact segment == reference")

    num, y = higgs_like(N_PARITY, seed)
    ds = on_device(from_numpy(num, None, y))
    seg = fit(f"parity hist segment n={N_PARITY}", params(**HIST), ds, seed)
    ker = fit(f"parity hist kernel n={N_PARITY}",
              params(backend="kernel", **HIST), ds, seed)
    same_trees(ker.trees, seg.trees, "parity/hist kernel == segment")
    src = MemmapRowSource.from_numpy(num, y, num_bins=255,
                                     path=str(work / "parity_bins.npy"),
                                     chunk_size=N_PARITY // 4)
    np.testing.assert_array_equal(
        src.edges, np.asarray(from_numpy(num, None, y).quantize(255)[1]))
    streamed = timed(f"parity hist fit_streamed n={N_PARITY}",
                     lambda: forest(params(**HIST), seed).fit_streamed(src))
    same_trees(streamed.trees, seg.trees,
               "parity/hist fit_streamed == fit")


def full_size(name: str, p, ds, test, seed: int):
    """`segment` and `kernel` fits of one mode at full size: AUC floor on
    both, node-for-node agreement observed."""
    import dataclasses
    seg = fit(f"{name} segment n={ds.n}", p, ds, seed, tree_batch=1)
    check_auc(seg, test, f"{name} segment")
    pk = dataclasses.replace(p, backend="kernel", leaf_pad=KERNEL_PAD)
    ker = fit(f"{name} kernel n={ds.n} leaf_pad={pk.leaf_pad}", pk, ds, seed)
    check_auc(ker, test, f"{name} kernel")
    agreement(ker.trees, seg.trees, f"{name} kernel vs segment n={ds.n}")
    return seg


def stream_full(seed: int, work: Path, test) -> None:
    """(c): a bin cache of N_STREAM rows built on local disk, then a
    checkpointed `fit_streamed` that reads it chunk by chunk."""
    from repro.core.dataset import MemmapRowSource

    num, y = timed(f"(c) generate HIGGS-like n={N_STREAM}",
                   lambda: higgs_like(N_STREAM, seed + 2))
    src = timed(f"(c) build memmap bin cache n={N_STREAM}",
                lambda: MemmapRowSource.from_numpy(
                    num, y, num_bins=255, path=str(work / "bins.npy"),
                    num_classes=2, chunk_size=CHUNK))
    del num
    rf = timed(f"(c) fit_streamed {STREAM_TREES} tree n={N_STREAM} "
               f"chunk={CHUNK} checkpointed",
               lambda: forest(params(**HIST), seed,
                              STREAM_TREES).fit_streamed(
                   src, checkpoint_dir=str(work / "ck")))
    check_auc(rf, test, "(c) fit_streamed")


def serve(rf, test, work: Path) -> None:
    """(d): save -> ForestServer.load -> requests == rf.predict_proba."""
    import numpy as np
    from repro.serve.engine import ForestServer

    path = work / "forest.npz"
    rf.packed.save(path)
    srv = ForestServer.load(path, warm_batch_sizes=(1, 64))
    num = np.asarray(test.num)
    rows = 256 + 64 * 64

    def requests():
        got = [np.asarray(srv.predict(num[i:i + 1])) for i in range(256)]
        return got + [np.asarray(srv.predict(num[lo:lo + 64]))
                      for lo in range(256, rows, 64)]

    got = timed("(d) 256 single-row + 64 batch-64 requests", requests)
    want = np.asarray(rf.predict_proba(num[:rows],
                                       np.zeros((rows, 0), np.int32)))
    np.testing.assert_array_equal(np.concatenate(got), want,
                                  err_msg="(d) server != predict_proba")
    log(f"(d) server answers == rf.predict_proba on {len(got)} requests")


def one_chip(seed: int, work: Path, dev) -> None:
    from repro.core.dataset import from_numpy

    parity(seed, work)
    num, y = timed(f"generate HIGGS-like n={N_HIST}+{N_TEST}",
                   lambda: higgs_like(N_HIST + N_TEST, seed + 1))
    test = from_numpy(num[N_HIST:], None, y[N_HIST:])
    # host-side: each fit device-puts its own copy, freed with it
    hist = full_size("(b) hist", params(**HIST),
                     from_numpy(num[:N_HIST], None, y[:N_HIST]), test, seed)
    serve(hist, test, work)
    del hist
    stream_full(seed, work, test)
    full_size("(a) exact", params(),
              from_numpy(num[:N_EXACT], None, y[:N_EXACT]), test, seed)
    peak_memory(dev)


# ---------------------------------------------------------------------------
# Four-chip phase: the sharded engines against one device
# ---------------------------------------------------------------------------

def check_spread(exact_eng, hist_eng, ds, mesh) -> None:
    """The sharded engines' per-row inputs and tables live on all four
    chips, split over both mesh axes, and the merges are collectives."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import presort, splits
    from repro.core.level.engines import LevelStatics

    n, m = ds.n, ds.m_num
    chips = set(mesh.devices.flat)
    Lp = 2
    leaf = jnp.asarray(np.arange(n) % Lp + 1, jnp.int32)
    w = jnp.ones((n,), jnp.float32)
    stats = splits.row_stats(ds.labels, w, 2, "classification")
    cand = jnp.ones((m, Lp + 1), bool)
    si = presort.presort_columns(ds.num)
    sv = presort.gather_sorted(ds.num, si)
    bins, edges = ds.quantize(255)
    runs = (
        ("exact 2-D", "all-gather",
         lambda a, b, c: exact_eng(a, b, leaf, w, stats, c, Lp, "gini",
                                   "classification", 1.0), (sv, si, cand)),
        ("hist", "all-reduce",
         lambda a, b, c: hist_eng(a, b, leaf, w, stats, c, Lp, "gini",
                                  "classification", 1.0), (bins, edges, cand)),
    )
    for name, collective, fn, args in runs:
        compiled = jax.jit(fn).lower(*args).compile()
        rows = compiled.input_shardings[0][0]
        check(set(rows.device_set) == chips, f"{name}: rows on {rows}")
        check(rows.shard_shape((m, n)) == (m // 2, n // 2),
              f"{name}: row shard {rows.shard_shape((m, n))}")
        check(collective in compiled.as_text(), f"{name}: no {collective}")
        gain, _ = compiled(*args)
        check(set(gain.sharding.device_set) == chips,
              f"{name}: gains on {gain.sharding}")
        log(f"{name}: (m, n) rows in shards of {rows.shard_shape((m, n))} "
            f"on {len(chips)} chips, merged by {collective}")
    st = LevelStatics(m_num=m, m_cat=0, max_arity=1, num_classes=2,
                      num_bins=255, impurity="gini", task="classification",
                      min_records=1.0)
    acc = hist_eng.stream_init(NUM_TREES, st, Lp)
    check(set(acc.sharding.device_set) == chips, f"stream acc {acc.sharding}")
    log(f"streamed hist: accumulator {acc.sharding.spec} shard "
        f"{acc.sharding.shard_shape(acc.shape)} on {len(chips)} chips")


def four_chips(seed: int, devices) -> None:
    """The exact 2-D, hist and streamed-hist sharded engines on a 2x2
    data x model mesh, each fit == the same fit on one device.  Shallow
    trees at a small n: the point is the mesh, and every sharded level
    program is a compile of its own.  The exact engine runs the `scan`
    backend inside each shard (a fraction of the `segment` backend's
    compile time there); the one-device fits run the default engines."""
    from repro.core import distributed
    from repro.core.dataset import ArrayRowSource, from_numpy
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(2, 2)
    num, y = higgs_like(N_SHARDED, seed + 3)
    ds = on_device(from_numpy(num, None, y))
    src = ArrayRowSource.from_dataset(ds, 255, chunk_size=N_SHARDED // 4)
    log(f"mesh: {dict(mesh.shape)} over {[d.id for d in mesh.devices.flat]}")
    exact_eng = distributed.make_2d_sharded_supersplit(mesh, backend="scan")
    hist_eng = distributed.make_hist_sharded_supersplit(mesh)
    check_spread(exact_eng, hist_eng, ds, mesh)
    pad = 1 << (SHARDED_DEPTH - 1)
    exact = params(max_depth=SHARDED_DEPTH, leaf_pad=pad)
    hist = params(max_depth=SHARDED_DEPTH, leaf_pad=pad, **HIST)
    runs = (("exact 2-D sharded", exact, lambda rf, e: rf.fit(ds, engine=e),
             exact_eng),
            ("hist sharded", hist, lambda rf, e: rf.fit(ds, engine=e),
             hist_eng),
            ("streamed hist sharded", hist,
             lambda rf, e: rf.fit_streamed(src, engine=e), hist_eng))
    for name, p, run, eng in runs:
        one = timed(f"{name} one device n={N_SHARDED}",
                    lambda: run(forest(p, seed), None))
        four = timed(f"{name} on 4 chips n={N_SHARDED}",
                     lambda: run(forest(p, seed), eng))
        same_trees(four.trees, one.trees, f"{name}: 4 chips == one device")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded engines on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("chip_smoke.py: no repro package beside this script; run "
                 "it from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    cache = compile_cache.configure()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke.py: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this smoke runs only on a TPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    dev = devices[0]
    COMPILES.install()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    cached = sum(1 for _ in Path(cache).glob("*")) if Path(cache).is_dir() else 0
    log(f"compile cache: {cache} ({cached} entries at start)")

    work = Path(tempfile.mkdtemp(prefix="chip_smoke-", dir=ROOT))
    try:
        if args.chips == 4:
            timed("total", lambda: four_chips(args.seed, devices))
        else:
            timed("total", lambda: one_chip(args.seed, work, dev))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
