#!/usr/bin/env python3
"""Runs of one cell with the library's own instrumentation read: host
spans, named scopes and counters (`repro.obs`, reduced by
`bench/trace_obs.py`).

    python3 bench/obs_cell.py --workload <cell> --seconds <s>
                              --runs on:<seed> off:<seed> ... [--check-once]

Each run is `bench/run.py`'s `run_cell` in this one process (set-up,
warm fit, window, check), on a TPU only.  An `on` run is traced as the
benchmark traces it, with the HLO protos kept for the scopes; an `off`
run is not traced, so `on` against `off` is the cost of tracing.
`--check-once` checks the trees against the reference in the first run
only (the others read `correct: null`).  One JSON line per run on
standard output: the rate, the per-fit host load, and for `on` runs the
three host-driver and level-step readings, the idle seconds by label
(`bench.fit` as `bench/trace_reduce.py` labels them, and by library
span), the scope coverage of the level programs, the window's counters
and `trace_obs.breakdown`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", nargs="+", required=True,
                    help="on:<seed> (traced) or off:<seed>")
    ap.add_argument("--check-once", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    import shutil

    import harness
    import jax
    import reference
    import run as bench_run
    import trace_obs
    import trace_reduce
    from repro import compile_cache, obs

    class Profile(harness.Profile):
        """`harness.Profile` with the HLO protos kept and the trace read
        by both reductions; the window's counter deltas beside them."""

        last = None

        def start(self):
            if not self.enabled:
                return
            self._c0 = obs.counters()
            self._dir = tempfile.mkdtemp(prefix="obs_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = True
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._window = harness.span("bench.window")
            self._window.__enter__()

        def stop(self, devices):
            if not self.enabled or self._window is None:
                return
            self._window.__exit__(None, None, None)
            self._window = None
            jax.profiler.stop_trace()
            self.counters = obs.delta(self._c0)
            ids = [d.id for d in devices]
            try:
                path = next(Path(self._dir).rglob("*.xplane.pb"))
                self.summary = trace_reduce.reduce(path, device_ids=ids)
                self.lib = trace_obs.reduce(path, device_ids=ids)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
            Profile.last = self

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.Profile = Profile
    cell = harness.load_cell(args.workload)
    T = cell["traffic"]["num_trees"]
    check_tree = reference.check_tree
    for k, item in enumerate(args.runs):
        mode, seed = item.split(":")
        checked = not (args.check_once and k)
        if not checked:
            reference.check_tree = lambda *a, **kw: {"gain_gap": 0.0,
                                                     "node_errors": 0}
        Profile.last = None
        t0 = harness.now()
        try:
            line = bench_run.run_cell(args.workload, int(seed),
                                      args.seconds, mode == "on",
                                      devices=devices[:1], cell=cell,
                                      t_start=t0)
        finally:
            reference.check_tree = check_tree
        info = line["info"]
        rec = {"cell": args.workload, "mode": mode, "seed": int(seed),
               "correct": line["correct"] if checked else None,
               "tree_rows_per_s": info["fits"] * T * info["rows"]
               / info["window_s"],
               "window_s": info["window_s"], "per_fit": info["per_fit"],
               "window_compiles": line["window_compiles"],
               "run_s": harness.now() - t0}
        p = Profile.last
        if p is not None:
            s, lib = p.summary, p.lib
            run = {"kind": "train", "trace": lib, "counters": p.counters}
            scopes = lib["device_scopes"]
            rec.update({
                "host_driver.busy_share.train": trace_obs.busy_share(run),
                "host_driver.exposed_idle_share.train":
                    trace_obs.exposed_idle_share(run),
                "level_step.supersplit_ns_per_row":
                    trace_obs.supersplit_ns_per_row(run),
                "idle_s": s["window_s"] - s["busy_s"],
                "idle_bench_fit_s": s["idle_gaps"].get("bench.fit", 0.0),
                "idle_by_span": lib["idle_gaps"],
                "level_leaf_s": lib["level_leaf_s"],
                "level_other_s": scopes.get(trace_obs.LEVEL_OTHER, 0.0),
                "level_module_s": sum(
                    v["seconds"] for m, v in s["modules"].items()
                    if "fused_level_step" in m),
                "counters": p.counters,
                "breakdown": trace_obs.breakdown(lib, top=16),
                "spans": lib["spans"],
                "device_ops": trace_reduce.breakdown(s)["device_ops"],
            })
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    t = time.perf_counter()
    rc = main()
    print(f"process {time.perf_counter() - t:.1f} s", file=sys.stderr)
    sys.exit(rc)
