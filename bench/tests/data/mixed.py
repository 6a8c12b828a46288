"""A mixed job for the tests: 4 count columns and 3 hashed categorical
columns (arities 3, 40 and 4096), binary labels that depend on both
kinds.  The widest column's ids are drawn from 300 values spread over
[0, 4096), so the categories a node meets are few and sparse."""
from __future__ import annotations

import numpy as np

ARITIES = (3, 40, 4096)
STRUCTURE_SEED = 7      # fixes the ids and their effects, not the rows


def generate(n: int, seed: int):
    """(num (n, 4) float32, cat (n, 3) int32, y (n,) int32)."""
    s = np.random.default_rng(STRUCTURE_SEED)
    pool = s.choice(ARITIES[2], 300, replace=False)
    effect = [s.normal(0, 1.0, a) for a in (ARITIES[0], ARITIES[1], 300)]
    rng = np.random.default_rng(seed)
    num = rng.poisson(3.0, (n, 4)).astype(np.float32)
    ids = [rng.integers(0, ARITIES[0], n), rng.integers(0, ARITIES[1], n),
           rng.integers(0, 300, n)]
    z = 0.4 * (num[:, 0] - 3.0) + sum(e[i] for e, i in zip(effect, ids))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.int32)
    cat = np.stack([ids[0], ids[1], pool[ids[2]]], axis=1).astype(np.int32)
    return num, cat, y
