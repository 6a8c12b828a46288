"""What every cell shares: the cell's files, the device, compile events,
host spans, the profiler window and the result line.

A cell is an entry of `BENCHMARK.json`'s `workloads`.  Everything that
belongs to one configuration, traffic mix or per-layer metric sits in a
file of its own, found by name:

  bench/configs/<config>.json   sizes, source, cuts and assumptions
  bench/configs/<config>.py     `generate(n, seed)`, the rows
  bench/traffic/<traffic>.json  the mix: its `kind` ("train" or "serve",
                                the module in bench/ that runs it), the
                                job's parameters and the limits of the
                                check that decides `correct`
  bench/metrics/<metric>.py     `read(run) -> float | None`

`generate` returns `(X, y)`, numeric columns (n, m) float32 and labels,
or `(num, cat, y)` for a configuration with categorical columns: `cat`
(n, m_cat) int32 ids.  Such a configuration's JSON gives
`categorical_columns` (m_cat) and `arities`, one per column: the
source's published cardinality, not the largest id drawn; ids lie in
[0, arity).  The library numbers numeric columns first, then the
categorical ones, and so does `bench/reference.py`.

A `train` traffic mix may give `mesh` {"data": d, "model": k} with d·k
the cell's `chips`: its fits then run on that mesh with the library's
sharded engines (`bench/train.py`).

`bench/later.json`, of `BENCHMARK.json`'s form, holds cells kept for a
later benchmark with their metrics: `load_cell` finds them too, so they
run by hand (`bench/sweep.py`, `bench/control.py`, the tests), while the
benchmark's checks run only the cells of `BENCHMARK.json`.
"""
from __future__ import annotations

import importlib.util
import json
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, its configuration and its traffic mix."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    later = json.loads((root / "bench" / "later.json").read_text())
    for key in ("workloads", "end_to_end", "per_layer"):
        spec[key] = spec[key] + later.get(key, [])
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / f"{cell['config']}.json")
                     .read_text())
    traffic = json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    gen = load_module(bench / "configs" / f"{cell['config']}.py",
                      f"config_{cell['config']}")

    def applies(metric):
        return workload in metric.get("workloads", [workload])
    return {"cell": cell, "config": cfg, "traffic": traffic, "bench": bench,
            "generate": gen.generate,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def rows(cell: dict, n: int, seed: int):
    """The configuration's rows from the seed: (X, cat, y, arities), with
    cat None and arities () for a numeric-only configuration."""
    out = cell["generate"](n, seed)
    if len(out) == 2:
        return out[0], None, out[1], ()
    return out[0], out[1], out[2], tuple(cell["config"]["arities"])


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


def forest_seed(seed: int, i: int) -> int:
    """The i-th forest seed of a run, below 2**31 (the library's seeds are
    32-bit signed)."""
    import numpy as np
    return int(np.random.SeedSequence([seed, i]).generate_state(1)[0] >> 1)


class CompileLog:
    """Seconds spent compiling or loading programs from the persistent
    cache, and how many, from `jax.monitoring` events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.secs, self.programs = 0.0, 0

    def _duration(self, event, secs, **kw):
        if event == self.EVENT:
            self.secs += secs
            self.programs += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def mark(self):
        return self.secs, self.programs


class HostLoad:
    """What the host did around a stretch of the window: wall seconds,
    this process's CPU seconds, the times it was preempted (involuntary
    context switches) and seconds in Python's garbage collector.  Read
    per fit, it tells a slow fit's cause: the device, the host's own
    work, a collection, or another process holding the cores."""

    def __init__(self):
        import gc
        self._gc_s, self._gc_t0 = 0.0, None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = now()
        elif self._gc_t0 is not None:
            self._gc_s += now() - self._gc_t0

    def mark(self) -> dict:
        import resource
        r = resource.getrusage(resource.RUSAGE_SELF)
        return {"wall_s": now(), "cpu_s": r.ru_utime + r.ru_stime,
                "preempted": r.ru_nivcsw, "gc_s": self._gc_s}

    def since(self, mark: dict) -> dict:
        return {k: v - mark[k] for k, v in self.mark().items()}

    def close(self):
        import gc
        gc.callbacks.remove(self._gc)


def span(name: str):
    """A host span in the profiler's trace (no cost worth naming when no
    trace is being taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Profile:
    """The `--trace 1` window: the profiler runs from `start` to `stop`
    and its trace is reduced to busy time, module times and idle gaps by
    host span."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.summary = None
        self._dir = None
        self._window = None

    def start(self):
        if not self.enabled:
            return
        import tempfile

        import jax
        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        # host spans (TraceMe) and device activity; no per-call Python
        # tracing, which would slow the host path it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._window = span("bench.window")
        self._window.__enter__()

    def stop(self, devices):
        if not self.enabled or self._window is None:
            return
        import shutil

        import jax
        import trace_reduce
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        try:
            path = next(Path(self._dir).rglob("*.xplane.pb"))
            self.summary = trace_reduce.reduce(
                path, device_ids=[d.id for d in devices])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def device_info(devices, trace_summary=None) -> dict:
    """`device` of the result line: as JAX reports it, the peak bytes in
    use on the fullest chip, and in a traced run the busy and window
    seconds."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
    if trace_summary is not None:
        info["busy_s"] = trace_summary["busy_s"]
        info["window_s"] = trace_summary["window_s"]
    return info


def read_metrics(metrics: list, run: dict, bench: Path = BENCH) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(bench / "metrics" / f"{m['name']}.py",
                             f"metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def checks_line(checks: dict) -> dict:
    """{name: {"value", "limit"}}; a check passes when value <= limit."""
    return {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}


def now() -> float:
    return time.perf_counter()

