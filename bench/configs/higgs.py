"""UCI HIGGS-shaped rows from a seed (the source lists the columns).

21 low-level kinematic columns (lepton pT/eta/phi, missing-energy
magnitude/phi, four jets' pT/eta/phi/b-tag) and 7 invariant-mass-like
columns derived from them; about 53% signal.  The b-tags take 3 values,
so exact search meets heavy ties.  Signal shifts the jet energy scale and
b-tag rates and adds a resonance to two of the masses, so a forest
separates the classes partly.  The distributions are assumptions
(`higgs.json` "assumed"); the column count, kinds and balance are the
source's.
"""
from __future__ import annotations

import numpy as np


def generate(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(num (n, 28) float32, y (n,) int32)."""
    rng = np.random.default_rng(seed)
    f32 = np.float32
    y = (rng.random(n) < 0.53).astype(np.int32)
    sig = y.astype(f32)

    def pt(scale):
        return (np.exp(0.6 * rng.standard_normal(n, f32)) * scale).astype(f32)

    def eta():
        return (1.2 * rng.standard_normal(n, f32)).astype(f32)

    def phi():
        return rng.uniform(-np.pi, np.pi, n).astype(f32)

    lep = [pt(1.0), eta(), phi()]
    met = [pt(0.9), phi()]
    jets = []
    for j in range(4):
        p = pt(1.0 - 0.15 * j) * (1.0 + 0.12 * sig)
        btag = rng.choice(np.array([0.0, 1.1, 2.2], f32), n,
                          p=[0.6, 0.2, 0.2])
        btag = np.where(sig.astype(bool) & (rng.random(n) < 0.25), f32(2.2),
                        btag)
        jets.append([p, eta(), phi(), btag])

    def mass(a, b):
        return np.sqrt(2 * a[0] * b[0] * np.maximum(
            np.cosh(a[1] - b[1]) - np.cos(a[2] - b[2]), 0)).astype(f32)

    res = np.where(sig > 0, 1.0 + 0.1 * rng.standard_normal(n, f32), 0.0)
    high = [mass(jets[0], jets[1]),
            mass(jets[0], jets[1]) + mass(jets[1], jets[2]),
            mass(lep, [met[0], np.zeros(n, f32), met[1]]),
            mass(jets[0], lep) + 0.5 * res,
            mass(jets[2], jets[3]) + res,
            mass(jets[1], jets[3]) + 0.5 * mass(lep, jets[2]),
            mass(jets[0], jets[2]) + mass(lep, jets[3])]
    cols = lep + met + [c for jet in jets for c in jet] + high
    return np.stack(cols, axis=1).astype(f32), y
