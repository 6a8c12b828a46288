"""Reduce a profiler trace to what the library's own spans and named scopes
say: where the time of a fit goes, on the host and on the device.

`trace_reduce.py` sees the library from outside (device busy time,
program and operation seconds, idle gaps by the benchmark's `bench.*`
spans).  This module reads the instrumentation inside it (`repro.obs`):

* spans: for each `repro.*` host span, its seconds inside the window,
  its self seconds (less the `bench.*`/`repro.*` spans nested in it on
  the same thread) and its count.  TraceMe arguments written into a name
  (`name#k=v#`) are stripped.
* idle_gaps: the first chip's idle gaps, each labelled with the innermost
  `bench.*` or `repro.*` span that holds the gap's middle, `no bench span`
  where none does.
* host_busy_s: the self seconds of the host-work spans: `repro.*`, less
  the waits (`repro.*.fetch`).
* exposed_idle_s: the first chip's idle seconds that fall in the self time
  of a host-work span: the device idle that the host driver causes.
* device_scopes: device seconds per innermost library scope
  (`jax.named_scope` names `level.*`, `presort.*`) of the leaf operations;
  container operations (`while`, `conditional`, `call`) are skipped, as
  the operations of their bodies are listed too.  Operations of a level
  program (`*level_step*`) with no library scope count as `level.other`;
  `level_leaf_s` is the leaf-operation seconds of the level programs.
  An operation's scope is its HLO `op_name`, read from the HLO protos the
  profiler writes into `/host:metadata` when `enable_hlo_proto` is on
  (`hlo_scopes`); without them `device_scopes` is empty.  A fusion whose
  own metadata is empty takes the scope of most of what it fuses.
  `scope_opcodes` splits each scope's seconds by opcode
  (`level.supersplit.tables:scatter`).

The readers at the end turn a run (a `run` dict of the harness's form,
with this reduction as `run["trace"]` and the window's `repro.obs`
counter deltas as `run["counters"]`, as `bench/obs_cell.py` builds it)
into the per-layer metrics of the host driver and of the level step's
table phase.  `bench/run.py` does not report them yet (PERF.md, Open
questions).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

import trace_reduce

LIB_PREFIX = "repro."
WAIT_SUFFIX = ".fetch"
CONTAINERS = frozenset({"while", "conditional", "call"})
LEVEL_OTHER = "level.other"
_SCOPE = re.compile(r"(?<![\w.])((?:level|presort)\.[a-z_]+(?:\.[a-z_]+)*)")
_OPCODE = re.compile(r"(?<![\w.%-])([a-z][a-z0-9-]*)\(")


def span_name(name: str) -> str:
    """A TraceMe name without the arguments encoded into it."""
    return name.split("#", 1)[0]


def scope_of(op_name: str):
    """The innermost library scope in an HLO `op_name`, or None."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def opcode_of(event_name: str) -> str:
    """The HLO opcode of a device operation event: parsed from the
    instruction text a TPU trace gives (`%while.5 = (...) while(...)`),
    or the instruction name's stem (`while.5` -> `while`)."""
    if " = " in event_name:
        m = _OPCODE.search(event_name.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return trace_reduce.op_name(event_name).split(".", 1)[0]


# ---------------------------------------------------------------------------
# HLO protos from the trace file (protobuf wire format, no generated code)
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _ids(v) -> list:
    """A repeated int64 field's values: packed (bytes) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _module_scopes(hlo_proto: bytes) -> dict:
    """{instruction: (opcode, scope)} of one HloProto: hlo_module (1) ->
    computations (3) -> instructions (2) -> name (1), opcode (2),
    metadata (7) -> op_name (2), called_computation_ids (38).

    XLA leaves the metadata of many fusions empty; such a fusion takes
    the scope most of the instructions it fuses carry (nested fusions
    resolved the same way)."""
    comps = {}        # computation id -> [(name, opcode, scope, calls)]
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, comp in _fields(module):
            if f2 != 3:
                continue
            cid, insts = None, []
            for f3, v in _fields(comp):
                if f3 == 5:
                    cid = v
                elif f3 == 2:
                    name = opcode = op_name = ""
                    calls = []
                    for f4, w in _fields(v):
                        if f4 == 1:
                            name = w.decode()
                        elif f4 == 2:
                            opcode = w.decode()
                        elif f4 == 7:
                            op_name = dict(_fields(w)).get(2, b"").decode()
                        elif f4 == 38:
                            calls += _ids(w)
                    insts.append((name, opcode, scope_of(op_name), calls))
            comps[cid] = insts

    memo = {}

    def fused_scope(cid):
        if cid not in memo:
            memo[cid] = None      # a cycle cannot occur; guards anyway
            votes = defaultdict(int)
            for _, opcode, scope, calls in comps.get(cid, ()):
                if scope is None and opcode == "fusion" and calls:
                    scope = fused_scope(calls[0])
                if scope is not None:
                    votes[scope] += 1
            memo[cid] = max(votes, key=votes.get) if votes else None
        return memo[cid]

    out = {}
    for insts in comps.values():
        for name, opcode, scope, calls in insts:
            if scope is None and opcode == "fusion" and calls:
                scope = fused_scope(calls[0])
            out[name] = (opcode, scope)
    return out


def hlo_scopes(data: bytes) -> dict:
    """{program name as the trace's module events give it ("jit_f(7)"):
    {instruction: (opcode, scope)}} from an `.xplane.pb`'s bytes: the
    `/host:metadata` plane (XSpace.planes (1), XPlane.name (2)) holds one
    event metadata (4) per program, named like its module events, with
    the serialized HloProto as the bytes value (6) of its stat (5)."""
    out = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        entries = []
        is_meta = False
        for pf, pv in _fields(plane):
            if pf == 2:
                is_meta = pv == b"/host:metadata"
            elif pf == 4:
                entries.append(pv)
        if not is_meta:
            continue
        for entry in entries:
            md = dict(_fields(entry)).get(2, b"")
            name, protos = "", []
            for mf, mv in _fields(md):
                if mf == 2:
                    name = mv.decode()
                elif mf == 5:
                    stat = dict(_fields(mv))
                    if isinstance(stat.get(6), bytes):
                        protos.append(stat[6])
            for proto in protos:
                out[name] = _module_scopes(proto)
    return out


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def _self_intervals(spans):
    """Per span of one thread ((start, end, name), sorted by start and
    then longest first): its intervals less those of the spans nested
    directly inside it, where it is the innermost span."""
    children = defaultdict(list)
    stack = []
    for k, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            children[stack[-1]].append((s, e))
        stack.append(k)
    out = []
    for k, (s, e, _) in enumerate(spans):
        pieces, cur = [], s
        for cs, ce in children[k]:
            if cs > cur:
                pieces.append((cur, cs))
            cur = max(cur, ce)
        if e > cur:
            pieces.append((cur, e))
        out.append(pieces)
    return out


def _innermost(pieces_by_thread, t) -> str:
    """The name of the shortest span that is innermost on its thread at
    time t, or `no bench span`."""
    best = None
    for pieces in pieces_by_thread:
        k = bisect.bisect_right(pieces, (t, float("inf"))) - 1
        if k >= 0 and pieces[k][0] <= t <= pieces[k][1]:
            if best is None or pieces[k][2] < best[0]:
                best = (pieces[k][2], pieces[k][3])
    return best[1] if best else "no bench span"


def _measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a, b):
    """Intersection of two sorted, merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def is_host_work(name: str) -> bool:
    return name.startswith(LIB_PREFIX) and not name.endswith(WAIT_SUFFIX)


def reduce_planes(planes, device_ids=None, hlo=None) -> dict:
    """The reduction over `ProfileData.planes` (or objects with the same
    `name` / `lines` / `events` / `start_ns` / `end_ns` fields); `hlo` is
    `hlo_scopes` of the same trace, or None."""
    threads, devices = [], {}
    for plane in planes:
        m = trace_reduce._DEVICE.match(plane.name)
        if m:
            if device_ids is None or int(m.group(1)) in device_ids:
                devices[int(m.group(1))] = plane
            continue
        for line in plane.lines:
            spans = [(ev.start_ns, ev.end_ns, span_name(ev.name))
                     for ev in line.events
                     if ev.name.startswith(("bench.", LIB_PREFIX))]
            if spans:
                threads.append(spans)
    windows = [(s, e) for spans in threads for s, e, n in spans
               if n == trace_reduce.WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError("trace has no bench.window span or no TPU plane")
    lo, hi = windows[0]

    # host spans, clipped to the window; on each thread, the pieces where
    # a span is the innermost one (its self time), sorted
    totals = defaultdict(lambda: [0.0, 0.0, 0])
    work, pieces_by_thread = [], []
    for spans in threads:
        spans = sorted(((max(s, lo), min(e, hi), n) for s, e, n in spans
                        if e > lo and s < hi
                        and n != trace_reduce.WINDOW_SPAN),
                       key=lambda x: (x[0], -x[1]))
        thread_pieces = []
        for (s, e, name), pieces in zip(spans, _self_intervals(spans)):
            thread_pieces += [(ps, pe, e - s, name) for ps, pe in pieces]
            if not name.startswith(LIB_PREFIX):
                continue
            rec = totals[name]
            rec[0] += (e - s) * 1e-9
            rec[1] += _measure(pieces) * 1e-9
            rec[2] += 1
            if is_host_work(name):
                work += pieces
        thread_pieces.sort()
        pieces_by_thread.append(thread_pieces)

    # the first chip's idle intervals, and each device op's scope
    n_dev = len(devices)
    scopes, scope_ops = defaultdict(float), defaultdict(float)
    level_leaf_s = 0.0
    idle = None
    for dev_id in sorted(devices):
        op_ev, mod_ev = [], []
        for line in devices[dev_id].lines:
            keep = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                    if ev.end_ns > lo and ev.start_ns < hi]
            if line.name == "XLA Ops":
                op_ev = keep
            elif line.name == "XLA Modules":
                mod_ev = sorted(keep)
        starts = [s for s, _, _ in mod_ev]
        for s, e, name in op_ev:
            k = bisect.bisect_right(starts, s) - 1
            if k < 0 or s >= mod_ev[k][1]:
                continue
            program = mod_ev[k][2]
            inst = trace_reduce.op_name(name)
            opcode, scope = (hlo or {}).get(program, {}).get(
                inst, (opcode_of(name), None))
            if opcode in CONTAINERS:
                continue
            secs = (min(e, hi) - max(s, lo)) / n_dev * 1e-9
            in_level = "level_step" in program
            if in_level:
                level_leaf_s += secs
            if scope is None and in_level and program in (hlo or {}):
                scope = LEVEL_OTHER
            if scope is not None:
                scopes[scope] += secs
                scope_ops[f"{scope}:{opcode}"] += secs
        if idle is None:
            busy = trace_reduce._union(
                trace_reduce._clip([(s, e) for s, e, _ in op_ev], lo, hi))
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s]

    gaps = defaultdict(float)
    for s, e in idle:
        gaps[_innermost(pieces_by_thread, (s + e) / 2)] += (e - s) * 1e-9
    work = trace_reduce._union(work)
    return {
        "window_s": (hi - lo) * 1e-9,
        "spans": {k: {"seconds": v[0], "self_s": v[1], "count": v[2]}
                  for k, v in totals.items()},
        "idle_gaps": dict(gaps),
        "host_busy_s": sum(v[1] for k, v in totals.items()
                           if is_host_work(k)),
        "exposed_idle_s": _measure(_intersect(idle, work)) * 1e-9,
        "device_scopes": dict(scopes),
        "scope_opcodes": dict(scope_ops),
        "level_leaf_s": level_leaf_s,
    }


def reduce(path, device_ids=None) -> dict:
    """`reduce_planes` of an `.xplane.pb` file, with its HLO protos."""
    from pathlib import Path

    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    return reduce_planes(ProfileData.from_serialized_xspace(data).planes,
                         device_ids, hlo_scopes(data))


def breakdown(summary: dict, top: int = 10) -> dict:
    """`device_scopes` (busiest first), the busiest scope opcodes and the
    host spans by self time."""
    def busiest(d):
        return sorted(d.items(), key=lambda kv: -kv[1])
    spans = sorted(summary["spans"].items(), key=lambda kv: -kv[1]["self_s"])
    return {"device_scopes": [[k, v] for k, v in
                              busiest(summary["device_scopes"])],
            "scope_opcodes": [[k, v] for k, v in
                              busiest(summary["scope_opcodes"])[:top]],
            "host_spans": [[k, v["self_s"], v["count"]]
                           for k, v in spans[:top]]}


# ---------------------------------------------------------------------------
# Readers of the per-layer metrics (a run of a training cell, traced)
# ---------------------------------------------------------------------------

def _train_trace(run):
    trace = run.get("trace")
    if run.get("kind") != "train" or not trace or "spans" not in trace:
        return None
    return trace


def busy_share(run):
    """host_driver.busy_share.train (%): self seconds of the host-work
    spans over the window."""
    trace = _train_trace(run)
    if trace is None:
        return None
    return 100.0 * trace["host_busy_s"] / trace["window_s"]


def exposed_idle_share(run):
    """host_driver.exposed_idle_share.train (%): device idle seconds in
    the self time of a host-work span, over the window."""
    trace = _train_trace(run)
    if trace is None:
        return None
    return 100.0 * trace["exposed_idle_s"] / trace["window_s"]


def supersplit_ns_per_row(run):
    """level_step.supersplit_ns_per_row (ns): device seconds under
    `level.supersplit` (tables and scoring included) per tree-row of the
    window's level dispatches (`level.tree_rows`)."""
    trace = _train_trace(run)
    rows = (run.get("counters") or {}).get("level.tree_rows", 0)
    if trace is None or not trace["device_scopes"] or rows <= 0:
        return None
    secs = sum(v for k, v in trace["device_scopes"].items()
               if k == "level.supersplit"
               or k.startswith("level.supersplit."))
    return 1e9 * secs / rows
