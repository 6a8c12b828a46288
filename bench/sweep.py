"""Find a serving cell's knee once, by a sweep of fixed rates on the chip.

    python bench/sweep.py --workload higgs.serve --rates 1000,2000,3000 \\
        --seconds 10 --seed 1

Runs the cell at each rate in turn (one process, the cell's own set-up
each time) and prints one JSON line per rate: p50 and p95 latency, the
median latency of the window's first and last quarter of requests, the
lag of the last answer behind the last request's due time, and whether
the server kept up.  It kept up when the backlog did not grow over the
window: the last answer came within 1% of the window after it was due
(a growing backlog leaves a lag that grows with the window), and the
last quarter's median wait is at most twice the first quarter's (a
single burst of bulk requests moves a median of a quarter of the window
by less than that).  The knee is the highest rate that kept up, with
every lower rate swept keeping up too; the cell is then fixed at about
four fifths of it.  The benchmark's own runs never search.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def kept_up(line: dict, seconds: float) -> bool:
    info = line["info"]
    return bool(info["lag_s"] <= 0.01 * seconds
            and info["last_quarter_p50_ms"]
            <= 2 * info["first_quarter_p50_ms"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH.parent / ".jax_cache")
    import jax

    import harness
    import run
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    knee, missed = None, False
    for rate in sorted(float(r) for r in args.rates.split(",")):
        cell = harness.load_cell(args.workload)
        cell["traffic"]["rate"] = rate
        line = run.run_cell(args.workload, args.seed, args.seconds, 0,
                            devices=devices[:1], cell=cell)
        ok = kept_up(line, args.seconds)
        missed = missed or not ok
        if not missed:
            knee = rate
        print(json.dumps({"rate": rate, **line["metrics"], **line["info"],
                          "kept_up": ok, "correct": line["correct"]}),
              flush=True)
    print(json.dumps({"knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
