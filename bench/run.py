#!/usr/bin/env python3
"""Run one cell of the benchmark once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n>
                         --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics come from
`BENCHMARK.json` and the files it names (see `bench/harness.py`).  It
runs on the machine it is started on and only on a TPU: without one, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.  The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` also
`breakdown`, and last `checks`, each number compared with its limit (also
the last lines of standard error).

JAX's persistent compilation cache is kept in `<checkout>/.jax_cache`,
a fixed path, handed to the library through JAX_COMPILATION_CACHE_DIR.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(workload, seed, seconds, trace, *, devices, cell=None,
             t_start=None):
    """One run of a cell on `devices`; returns the result line's dict.

    `cell` (from `harness.load_cell`) may be given with changed traffic
    parameters, which the tests use to run a cell small on the CPU."""
    import harness
    import trace_reduce
    t_start = harness.now() if t_start is None else t_start
    cell = cell or harness.load_cell(workload)
    compiles = harness.CompileLog().install()
    prof = harness.Profile(bool(trace))
    kind = cell["traffic"]["kind"]
    traffic_kind = harness.load_module(cell["bench"] / f"{kind}.py",
                                       f"kind_{kind}")
    window = {}

    def window_start():
        window["setup_s"] = harness.now() - t_start
        window["setup_compile_s"] = compiles.mark()[0]

    ctx = dict(cell, seed=seed, seconds=seconds, devices=devices,
               compiles=compiles, profile=prof, window_start=window_start)
    res = traffic_kind.run(ctx)
    checks = res["checks"]
    correct = all(v <= lim for v, lim in checks.values())
    if trace:
        run = {"kind": kind, "trace": prof.summary, "device": res["device"],
               "peaks": harness.peaks(res["device"]["kind"]),
               "setup_compile_s": window["setup_compile_s"],
               **res.get("layer_inputs", {})}
        metrics = harness.read_metrics(cell["per_layer"], run, cell["bench"])
    else:
        e2e = dict(res["end_to_end"], setup_s=window["setup_s"])
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in e2e.items() if k in units}
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics,
            "device": res["device"], "window_compiles": res["window_compiles"],
            "info": res["info"]}
    if trace and prof.summary is not None:
        line["breakdown"] = trace_reduce.breakdown(prof.summary)
    line["checks"] = harness.checks_line(checks)
    return line


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    chips = int(cell["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 2
    from repro import compile_cache
    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    line = run_cell(args.workload, args.seed, args.seconds, args.trace,
                    devices=devices[:chips], cell=cell, t_start=T_START)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
