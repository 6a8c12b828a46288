"""UCI Covertype-shaped rows from a seed.

581,012 rows, 54 columns as the source ships them: 10 integer-valued
cartographic columns, then 4 wilderness-area and 40 soil-type one-hot
columns; 7 classes at exactly the source's counts.  How the columns carry
the class is assumed (`covertype.json` "assumed"): elevation by a
per-class normal, the other cartographic columns by small per-class
shifts, and wilderness area and soil type by per-class distributions
drawn once from a constant seed, so every run trains on data of the same
structure and only the rows differ with the run's seed.
"""
from __future__ import annotations

import numpy as np

CLASS_COUNTS = (211840, 283301, 35754, 2747, 9493, 17367, 20510)
ELEVATION = ((3129, 157), (2920, 189), (2394, 197), (2223, 103),
             (2787, 96), (2420, 188), (3362, 110))
# (low, high, mean, sd, per-class shift in sd) of the other 9 columns
NUMERIC = (
    ("aspect", 0, 360, 156, 112, 0.15),
    ("slope", 0, 66, 14, 7.5, 0.3),
    ("hdist_hydrology", 0, 1397, 269, 212, 0.2),
    ("vdist_hydrology", -173, 601, 46, 58, 0.2),
    ("hdist_roadways", 0, 7117, 2350, 1559, 0.4),
    ("hillshade_9am", 0, 255, 212, 27, 0.2),
    ("hillshade_noon", 0, 255, 223, 20, 0.2),
    ("hillshade_3pm", 0, 255, 143, 38, 0.2),
    ("hdist_fire", 0, 7173, 1980, 1324, 0.4),
)
STRUCTURE_SEED = 31   # fixes the per-class structure, not the rows


def _structure(C: int):
    rng = np.random.default_rng(STRUCTURE_SEED)
    shifts = rng.standard_normal((len(NUMERIC), C))
    wild = rng.dirichlet(np.full(4, 0.6), C)        # (C, 4)
    soil = rng.dirichlet(np.full(40, 0.25), C)      # (C, 40)
    return shifts, wild, soil


def generate(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(num (n, 54) float32, y (n,) int32); n <= 581,012 takes the
    source's class shares, exact at the full n."""
    C = len(CLASS_COUNTS)
    total = sum(CLASS_COUNTS)
    counts = [c * n // total for c in CLASS_COUNTS]
    counts[1] += n - sum(counts)
    rng = np.random.default_rng(seed)
    y = rng.permutation(np.repeat(np.arange(C, dtype=np.int32), counts))
    shifts, wild, soil = _structure(C)
    mu, sd = (np.asarray(v, np.float64)[y] for v in zip(*ELEVATION))
    cols = [np.clip(np.rint(mu + sd * rng.standard_normal(n)), 1859, 3858)]
    for k, (_, lo, hi, mean, s, shift) in enumerate(NUMERIC):
        loc = mean + shift * s * shifts[k][y]
        cols.append(np.clip(np.rint(loc + s * rng.standard_normal(n)),
                            lo, hi))
    onehot = []
    for probs in (wild, soil):
        cum = np.cumsum(probs, axis=1)[y]             # (n, k)
        pick = (rng.random(n)[:, None] > cum).sum(1)
        pick = np.minimum(pick, probs.shape[1] - 1)
        onehot.append(np.eye(probs.shape[1], dtype=np.float32)[pick])
    num = np.concatenate([np.stack(cols, 1).astype(np.float32)] + onehot,
                         axis=1)
    return num, y
