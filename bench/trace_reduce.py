"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics
read.

Device planes are `/device:TPU:<id>`; on each, the "XLA Ops" line holds
every operation the chip ran and the "XLA Modules" line every program
execution.  The window is the benchmark's own host span `bench.window`.
Within it:

* busy: the union of the operation intervals, averaged over the chips
  used; idle is the rest of the window;
* modules: device seconds and executions per program name (the jit name,
  without the trailing execution id);
* ops: device seconds per `<program>:<operation>` (an operation belongs
  to the program execution that contains its start);
* idle gaps of the first chip, each labelled with the innermost of the
  benchmark's host spans (`bench.*`) that contains the gap's middle, and
  summed per label.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"\(\d+\)$")


def _union(intervals):
    """Sorted, merged (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(name: str) -> str:
    """A program's name without its execution id: `jit_step(123)` ->
    `jit_step`."""
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """An operation's HLO name: `%fusion.3 = f32[...] fusion(...)` ->
    `fusion.3`."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes, device_ids=None) -> dict:
    """The reduction over `ProfileData.planes` (or any objects with the
    same `name` / `lines` / `events` / `start_ns` / `end_ns` fields)."""
    host_spans, devices = [], {}
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if m:
            if device_ids is None or int(m.group(1)) in device_ids:
                devices[int(m.group(1))] = plane
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError("trace has no bench.window span or no TPU plane")
    lo, hi = windows[0]
    spans = [(s, e, n) for s, e, n in host_spans if n != WINDOW_SPAN]

    busy = {}
    modules = defaultdict(lambda: [0.0, 0])
    ops = defaultdict(float)
    gaps_dev = None
    for dev_id in sorted(devices):
        op_ev, mod_ev = [], []
        for line in devices[dev_id].lines:
            keep = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                    if ev.end_ns > lo and ev.start_ns < hi]
            if line.name == "XLA Ops":
                op_ev = keep
            elif line.name == "XLA Modules":
                mod_ev = sorted(keep)
        for s, e, name in mod_ev:
            rec = modules[module_name(name)]
            rec[0] += (min(e, hi) - max(s, lo)) / len(devices) * 1e-9
            rec[1] += 1
        starts = [s for s, _, _ in mod_ev]
        for s, e, name in op_ev:
            k = bisect.bisect_right(starts, s) - 1
            mod = module_name(mod_ev[k][2]) if k >= 0 and s < mod_ev[k][1] \
                else "?"
            key = f"{mod}:{op_name(name)}"
            ops[key] += (min(e, hi) - max(s, lo)) / len(devices) * 1e-9
        merged = _union(_clip([(s, e) for s, e, _ in op_ev], lo, hi))
        busy[dev_id] = sum(e - s for s, e in merged) * 1e-9
        if gaps_dev is None:
            gaps_dev = merged
    gaps = defaultdict(float)
    edges = [lo] + [x for iv in gaps_dev for x in iv] + [hi]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        inside = [(se - ss, n) for ss, se, n in spans if ss <= mid <= se]
        label = min(inside)[1] if inside else "no bench span"
        gaps[label] += (e - s) * 1e-9
    window_s = (hi - lo) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(busy),
        "modules": {k: {"seconds": v[0], "count": v[1]}
                    for k, v in modules.items()},
        "ops": dict(ops),
        "idle_gaps": dict(gaps),
    }


def reduce(path, device_ids=None) -> dict:
    """`reduce_planes` of an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes,
                         device_ids)


def breakdown(summary: dict, top: int = 10) -> dict:
    """The result line's `breakdown`: the device operations and programs
    that took most time, and the longest idle gaps by host span."""
    busiest = sorted(summary["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in busiest],
            "idle_gaps": [[k, v] for k, v in gaps]}
