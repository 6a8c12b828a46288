"""The reduction of the library's spans and scopes (`trace_obs.py`) on a
hand-made trace, on hand-encoded HLO protos, and on a small trace
recorded on a TPU v5e (`data/sample_scopes.xplane.pb`)."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import pytest  # noqa: E402

import trace_obs  # noqa: E402

SAMPLE = Path(__file__).resolve().parent / "data" / "sample_scopes.xplane.pb"
LEVEL = "jit__fused_level_step_batched(5)"


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def hand_trace():
    """A window [1000, 3000] with one fit: the host preps a level, sends
    it, waits on it and books it; the device runs a `while` whose body is
    two operations, then one more program; an argument-suffixed span
    last.  Host and device idle in places chosen by hand."""
    host = plane("/host:CPU", python=[
        ev("bench.window", 1000, 3000), ev("bench.fit", 1000, 2700),
        ev("repro.fit", 1000, 2400),
        ev("repro.level.prep", 1100, 1300),
        ev("repro.level.dispatch", 1300, 1350),
        ev("repro.level.fetch", 1350, 1800),
        ev("repro.level.book", 1800, 2000),
        ev("repro.level.prep#depth=1,batch=0:2#", 2000, 2100),
        ev("PjitFunction(f)", 1300, 1340), ev("other", 0, 5000)])
    dev = plane("/device:TPU:0",
                XLA_Ops=[ev("fusion.1", 900, 1100),
                         ev("%while.3 = (s32[]) while(s32[] %p), body=%b",
                            1350, 1700),
                         ev("fusion.7", 1360, 1500),
                         ev("fusion.8", 1500, 1690),
                         ev("sort.2", 2100, 2200)],
                XLA_Modules=[ev("jit_presort(4)", 900, 1100),
                             ev(LEVEL, 1340, 1750),
                             ev("jit_other(6)", 2100, 2200)])
    return [host, dev]


HLO = {LEVEL: {"while.3": ("while", None),
               "fusion.7": ("fusion", "level.supersplit.tables"),
               "fusion.8": ("fusion", None)},
       "jit_other(6)": {"sort.2": ("sort", "presort.bin_columns")}}


def test_spans_self_time_and_args_stripped():
    s = trace_obs.reduce_planes(hand_trace(), device_ids=[0], hlo=HLO)
    sp = s["spans"]
    assert sp["repro.fit"]["seconds"] == pytest.approx(1400e-9)
    # less prep, dispatch, fetch, book and the second prep (1000 ns)
    assert sp["repro.fit"]["self_s"] == pytest.approx(400e-9)
    assert sp["repro.level.prep"]["count"] == 2          # `#...#` stripped
    assert sp["repro.level.prep"]["self_s"] == pytest.approx(300e-9)
    assert set(sp) == {"repro.fit", "repro.level.prep",
                       "repro.level.dispatch", "repro.level.fetch",
                       "repro.level.book"}
    # host work: every library self second but the wait (450 ns)
    assert s["host_busy_s"] == pytest.approx(
        sum(v["self_s"] for v in sp.values()) - 450e-9)


def test_idle_gaps_take_library_labels_and_keep_bench_ones():
    s = trace_obs.reduce_planes(hand_trace(), device_ids=[0], hlo=HLO)
    # busy [1000, 1100], [1350, 1700], [2100, 2200]; idle gaps:
    # [1100, 1350] mid 1225 in prep; [1700, 2100] mid 1900 in book;
    # [2200, 3000] mid 2600 in bench.fit after repro.fit has ended: no
    # span of the library holds it, so the benchmark's label stays
    assert s["idle_gaps"] == {
        "repro.level.prep": pytest.approx(250e-9),
        "repro.level.book": pytest.approx(400e-9),
        "bench.fit": pytest.approx(800e-9)}
    planes = hand_trace()
    planes[0].lines[0].events[1] = ev("bench.fit", 1000, 2400)
    s = trace_obs.reduce_planes(planes, device_ids=[0], hlo=HLO)
    assert s["idle_gaps"]["no bench span"] == pytest.approx(800e-9)
    # device idle in host work (not in the wait): prep [1100, 1300],
    # dispatch [1300, 1350], fetch's tail [1700, 1800] is a wait, book
    # [1800, 2000], second prep [2000, 2100], repro.fit [2200, 2400]
    assert s["exposed_idle_s"] == pytest.approx(
        (200 + 50 + 200 + 100 + 200) * 1e-9)


def test_scope_seconds_skip_containers():
    s = trace_obs.reduce_planes(hand_trace(), device_ids=[0], hlo=HLO)
    # the while (350 ns) holds fusion.7 and fusion.8: counted once
    assert s["device_scopes"] == {
        "level.supersplit.tables": pytest.approx(140e-9),
        "level.other": pytest.approx(190e-9),
        "presort.bin_columns": pytest.approx(100e-9)}
    assert s["level_leaf_s"] == pytest.approx(330e-9)
    assert s["scope_opcodes"] == {
        "level.supersplit.tables:fusion": pytest.approx(140e-9),
        "level.other:fusion": pytest.approx(190e-9),
        "presort.bin_columns:sort": pytest.approx(100e-9)}
    b = trace_obs.breakdown(s)
    assert b["device_scopes"][0][0] == "level.other"
    assert b["host_spans"][0][0] == "repro.level.fetch"
    # without HLO protos there are no scopes, and no container either
    s = trace_obs.reduce_planes(hand_trace(), device_ids=[0])
    assert s["device_scopes"] == {}
    assert s["level_leaf_s"] == pytest.approx(330e-9)


@pytest.mark.parametrize("name,opcode", [
    ("%while.3 = (s32[], f32[8]{0:T(1024)}) while((s32[]) %t), body=%b",
     "while"),
    ("%fusion.2 = f32[4096]{0:T(1024)S(1)} fusion(s32[8]{0} %a), "
     "kind=kLoop", "fusion"),
    ("conditional.4", "conditional"),
])
def test_opcode_of(name, opcode):
    assert trace_obs.opcode_of(name) == opcode


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_fused_level_step_batched)/vmap(level.supersplit)/"
     "level.supersplit.tables/gather", "level.supersplit.tables"),
    ("jit(_fused_level_step_batched)/level.partition/lt", "level.partition"),
    ("jit(bin_columns)/presort.bin_columns/vmap()/while",
     "presort.bin_columns"),
    ("jit(_fused_level_step_batched)/vmap(jit(_where))/select_n", None),
])
def test_scope_of(op_name, scope):
    assert trace_obs.scope_of(op_name) == scope


def _field(num, payload):
    """One length-delimited protobuf field."""
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _varint(x):
    out = b""
    while True:
        b, x = x & 0x7F, x >> 7
        out += bytes([b | (0x80 if x else 0)])
        if not x:
            return out


def test_hlo_scopes_from_encoded_protos():
    inst = (_field(1, "fusion.7") + _field(2, "fusion")
            + _field(7, _field(2, "jit(f)/vmap(level.merge)/argmax")))
    bare = _field(1, "copy.1") + _field(2, "copy")
    module = _field(1, "jit_f") + _field(3, _field(1, "main")
                                         + _field(2, inst) + _field(2, bare))
    stat = _varint(1 << 3) + _varint(1) + _field(6, _field(1, module))
    meta = _field(2, "jit_f(7)") + _field(5, stat)
    entry = _varint(1 << 3) + _varint(7) + _field(2, meta)
    space = (_field(1, _field(2, "/host:CPU"))
             + _field(1, _field(2, "/host:metadata") + _field(4, entry)))
    assert trace_obs.hlo_scopes(space) == {
        "jit_f(7)": {"fusion.7": ("fusion", "level.merge"),
                     "copy.1": ("copy", None)}}


def _run(kind="train", trace=True, rows=4096):
    s = trace_obs.reduce_planes(hand_trace(), device_ids=[0], hlo=HLO)
    return {"kind": kind, "trace": s if trace else None,
            "counters": {"level.tree_rows": rows}}


@pytest.mark.parametrize("reader", [trace_obs.busy_share,
                                    trace_obs.exposed_idle_share,
                                    trace_obs.supersplit_ns_per_row])
def test_readers_need_a_traced_training_run(reader):
    assert reader(_run()) > 0
    assert reader(_run(kind="serve")) is None
    assert reader(_run(trace=False)) is None
    assert reader({"kind": "train", "trace": {"window_s": 1.0}}) is None


def test_reader_values():
    run = _run()
    assert trace_obs.busy_share(run) == pytest.approx(
        100 * run["trace"]["host_busy_s"] / 2000e-9)
    assert trace_obs.exposed_idle_share(run) == pytest.approx(
        100 * 750e-9 / 2000e-9)
    assert trace_obs.supersplit_ns_per_row(run) == pytest.approx(140 / 4096)
    assert trace_obs.supersplit_ns_per_row(_run(rows=0)) is None


def test_recorded_chip_trace():
    """One hist fit of two trees, depth 4, 2^14 rows, recorded on a TPU
    v5e with HLO protos and trimmed to what the reduction reads."""
    import trace_reduce
    s = trace_obs.reduce(SAMPLE, device_ids=[0])
    outside = trace_reduce.reduce(SAMPLE, device_ids=[0])
    spans = s["spans"]
    assert {"repro.fit", "repro.fit.presort", "repro.fit.quantize",
            "repro.forest.batch", "repro.forest.assemble",
            "repro.forest.pack"} <= set(spans)
    # one batch of depth 4: four dispatched levels, each fetched
    assert spans["repro.level.dispatch"]["count"] == 4
    assert spans["repro.level.fetch"]["count"] == 4
    # every idle second the benchmark's spans put in `bench.fit` now
    # has a library span
    assert set(outside["idle_gaps"]) == {"bench.fit"}
    assert all(k.startswith("repro.") for k in s["idle_gaps"])
    assert sum(s["idle_gaps"].values()) == pytest.approx(
        sum(outside["idle_gaps"].values()))
    # the level programs' leaf operations carry their phases' scopes:
    # at least 95% outside `level.other`, never more than the programs
    level = [v for k, v in s["device_scopes"].items()
             if k.startswith("level.")]
    named = sum(level) - s["device_scopes"].get("level.other", 0.0)
    assert named >= 0.95 * s["level_leaf_s"]
    assert sum(level) <= outside["modules"][
        "jit__fused_level_step_batched"]["seconds"] + 1e-12
    assert {"level.draw", "level.supersplit.tables",
            "level.supersplit.score", "level.merge", "level.reassign",
            "level.totals"} <= set(s["device_scopes"])
    assert s["device_scopes"]["presort.bin_columns"] > 0
    assert 0 < s["exposed_idle_s"] <= s["window_s"] - outside["busy_s"]


def test_fusion_without_metadata_takes_its_fused_scope():
    """XLA leaves many fusions' metadata empty: such a fusion takes the
    scope most of its fused instructions carry."""
    def inst(name, opcode, op_name="", calls=()):
        body = _field(1, name) + _field(2, opcode)
        if op_name:
            body += _field(7, _field(2, op_name))
        for c in calls:
            body += _varint(38 << 3) + _varint(c)
        return _field(2, body)

    def comp(cid, *insts):
        return _field(3, _varint(5 << 3) + _varint(cid) + b"".join(insts))

    fused = comp(2, inst("a", "add", "jit(f)/level.totals/add"),
                 inst("b", "mul", "jit(f)/level.totals/mul"),
                 inst("c", "copy", "jit(f)/level.merge/copy"),
                 inst("d", "parameter"))
    nested = comp(3, inst("e", "fusion", "", [2]),
                  inst("g", "neg", "jit(f)/level.draw/neg"),
                  inst("h", "abs", "jit(f)/level.totals/abs"))
    main = comp(1, inst("fusion.1", "fusion", "", [2]),
                inst("fusion.2", "fusion", "", [3]),
                inst("fusion.3", "fusion", "jit(f)/level.merge/x", [2]))
    proto = _field(1, _field(1, "jit_f") + fused + nested + main)
    scopes = trace_obs._module_scopes(proto)
    assert scopes["fusion.1"] == ("fusion", "level.totals")
    assert scopes["fusion.2"] == ("fusion", "level.totals")  # e and h : g
    assert scopes["fusion.3"] == ("fusion", "level.merge")   # its own
