"""`repro.obs`: the counter registry, and the library's host spans as a
profiler trace taken on the CPU records them."""
import os
import sys
import threading
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.core import tree as tree_lib
from repro.core.dataset import from_numpy
from repro.core.forest import RandomForest

FIT_SPANS = {"repro.fit", "repro.fit.presort", "repro.forest.batch",
             "repro.forest.assemble", "repro.forest.pack"}
LEVEL_SPANS = {"repro.level.prep", "repro.level.dispatch",
               "repro.level.fetch", "repro.level.book"}


def test_counter_registry_count_snapshot_delta():
    snap = obs.counters()
    obs.count("test.obs.calls")
    obs.count("test.obs.calls", 2)
    obs.count("test.obs.seconds", 0.25)
    assert obs.counter("test.obs.calls") == snap.get("test.obs.calls", 0) + 3
    assert obs.counter("test.obs.never") == 0
    d = obs.delta(snap)
    assert d["test.obs.calls"] == 3
    assert d["test.obs.seconds"] == pytest.approx(0.25)
    # a snapshot is a copy: later counts do not reach it
    again = obs.counters()
    obs.count("test.obs.calls")
    assert again["test.obs.calls"] == obs.counter("test.obs.calls") - 1
    assert obs.delta(again, again) == {k: 0 for k in again}


def test_counts_from_many_threads_are_not_lost():
    """Servers count from their request threads: no update may be lost."""
    workers, per = min(64, 2 * (os.cpu_count() or 1) + 1), 2000
    before = obs.counter("test.obs.threads")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda: [obs.count("test.obs.threads")
                            for _ in range(per)]) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert obs.counter("test.obs.threads") - before == workers * per


@pytest.fixture(scope="module")
def ds():
    rng = np.random.default_rng(0)
    num = rng.normal(size=(512, 6)).astype(np.float32)
    y = ((num[:, 0] + num[:, 1] * num[:, 2]) > 0).astype(np.int32)
    return from_numpy(num, None, y)


def _traced(tmp_path, fit):
    """(name, stats) of every `repro.*` event of a trace around `fit()`."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fit()
    finally:
        jax.profiler.stop_trace()
    path = next(Path(tmp_path).rglob("*.xplane.pb"))
    return [(ev.name, dict(ev.stats))
            for plane in ProfileData.from_file(str(path)).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.")]


def _per_depth(events):
    return Counter(int(st["depth"]) for name, st in events
                   if name in LEVEL_SPANS)


def test_fit_spans_in_trace_and_constant_per_level(tmp_path, ds):
    """A batched hist fit writes the fit, batch and level spans; each
    level writes the same spans whatever its number of open leaves."""
    p = tree_lib.TreeParams(max_depth=4, split_mode="hist", num_bins=16)

    def fit():
        return RandomForest(p, num_trees=2, seed=3, tree_batch=2).fit(ds)
    fit()                                                # warm the jits
    snap = obs.counters()
    events = _traced(tmp_path, fit)
    d = obs.delta(snap)
    names = {name for name, _ in events}
    assert FIT_SPANS | LEVEL_SPANS | {"repro.fit.quantize"} <= names
    # one batched dispatch per level above the leaves, the counters with
    # them: two trees of 512 rows in every level's state
    assert d["level.dispatches"] == p.max_depth
    assert d["level.tree_rows"] == 2 * 512 * p.max_depth
    assert d["level.fetch_bytes"] > 0
    assert d.get("level.traces", 0) == 0                 # warm
    per_depth = _per_depth(events)
    # 1, 2, 4, 8 open leaves, the same five spans a level (at the root a
    # fetch of the root totals in place of a deferred book); the last
    # depth dispatches nothing
    assert {per_depth[k] for k in range(p.max_depth)} == {5}
    assert per_depth[p.max_depth] == 2


def test_per_tree_builder_writes_the_level_spans(tmp_path, ds):
    p = tree_lib.TreeParams(max_depth=3)

    def fit():
        return RandomForest(p, num_trees=2, seed=3, tree_batch=1).fit(ds)
    fit()
    snap = obs.counters()
    events = _traced(tmp_path, fit)
    d = obs.delta(snap)
    names = {name for name, _ in events}
    assert FIT_SPANS | LEVEL_SPANS <= names
    assert d.get("level.dispatches", 0) == 0
    assert d["level.tree_dispatches"] == 2 * p.max_depth
    per_depth = _per_depth(events)
    # two trees, four spans a dispatched level each, and at the root the
    # fetch of the root totals
    assert per_depth[0] == 10
    assert {per_depth[k] for k in range(1, p.max_depth)} == {8}


def test_streamed_fit_writes_chunk_and_checkpoint_spans(tmp_path, ds):
    """The streamed chunk loop names its read, stage, dispatch, fetch and
    score; a checkpointed fit times its writes as span and counter."""
    from repro.core.dataset import ArrayRowSource
    p = tree_lib.TreeParams(max_depth=3, split_mode="hist", num_bins=16,
                            bagging="none")
    src = ArrayRowSource.from_dataset(ds, p.num_bins, chunk_size=200)

    def fit():
        return RandomForest(p, num_trees=2, seed=5).fit_streamed(
            src, checkpoint_dir=str(tmp_path / "ck"))
    fit()
    snap = obs.counters()
    events = _traced(tmp_path / "trace", fit)
    d = obs.delta(snap)
    names = Counter(name for name, _ in events)
    assert {"repro.stream.read", "repro.stream.stage",
            "repro.stream.dispatch", "repro.stream.fetch",
            "repro.stream.score", "repro.ckpt.write",
            "repro.forest.batch", "repro.forest.pack"} <= set(names)
    # 512 rows in chunks of 200: three chunks a level, one span of each
    # kind per chunk, one dispatch counted per chunk
    assert names["repro.stream.dispatch"] == d["stream.chunk_dispatches"]
    assert d["stream.chunk_dispatches"] % 3 == 0
    assert names["repro.stream.read"] == names["repro.stream.dispatch"]
    assert d.get("stream.traces", 0) == 0                # warm
    assert d["ckpt.write_s"] > 0


def test_server_predict_writes_its_four_spans(tmp_path, ds):
    from repro.serve.engine import ForestServer
    rf = RandomForest(tree_lib.TreeParams(max_depth=3), num_trees=2,
                      seed=1).fit(ds)
    path = str(tmp_path / "model.npz")
    rf._packed_forest().save(path)
    srv = ForestServer.load(path, warm_batch_sizes=(4,))
    x = np.asarray(ds.num[:4])
    events = _traced(tmp_path / "trace", lambda: srv.predict(x))
    names = [name for name, _ in events]
    assert names.count("repro.serve.validate") == 1
    assert {"repro.serve.transfer", "repro.serve.descent",
            "repro.serve.fetch"} <= set(names)
    out = srv.predict(x)
    assert isinstance(out, np.ndarray) and out.shape == (4, 2)
