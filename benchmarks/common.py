"""Shared benchmark utilities: timing + CSV emission.

Every benchmark prints ``name,us_per_call,derived`` rows (repo convention).
"""
from __future__ import annotations

import time

import jax


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall time in µs per call (blocks on jax outputs)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)
