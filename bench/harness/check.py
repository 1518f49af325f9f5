"""The comparison that decides ``correct``: the pixels the server served,
on cache groups sampled from the seed, against the plain Lumina replay
(``references/lumina.py``) of the same frames.

Every frame the session delivered, from admission on, is compared on the
sampled groups.  Two numbers are read:

* ``rmse``: the worst root-mean-square error of one group in one frame;
* ``off_share``: the share of compared pixels whose error in some channel
  exceeds one display level (1/255).

Limits are per cell, in ``bench/limits/<workload>.json``.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LEVEL = 1.0 / 255.0
NUMBERS = ('rmse', 'off_share')


class Gaps:
    """Accumulates the gaps of served groups against the reference."""

    def __init__(self):
        self.rmse = 0.0
        self.off = 0
        self.pixels = 0
        self.bad = False

    def add(self, served: np.ndarray, ref: np.ndarray) -> None:
        served = np.asarray(served, np.float64)
        ref = np.asarray(ref, np.float64)
        if served.shape != ref.shape or not np.isfinite(served).all():
            self.bad = True
            return
        err = np.abs(served - ref)
        per = np.sqrt((err ** 2).mean(axis=(-3, -2, -1)))
        self.rmse = max(self.rmse, float(per.max()))
        self.off += int((err.max(axis=-1) > LEVEL).sum())
        self.pixels += int(np.prod(err.shape[:-1]))

    def numbers(self) -> dict:
        if self.bad or not self.pixels:
            return {k: float('inf') for k in NUMBERS}
        return {'rmse': self.rmse, 'off_share': self.off / self.pixels}


def load_limits(bench_dir: Path, workload: str) -> dict:
    path = bench_dir / 'limits' / f'{workload}.json'
    return {k: float(v['limit'])
            for k, v in json.loads(path.read_text())['numbers'].items()}


def judge(numbers: dict, limits: dict) -> bool:
    return all(numbers.get(k, float('inf')) <= lim
               for k, lim in limits.items())


def sample_groups(width: int, height: int, g: int, count: int,
                  seed: int) -> list:
    """``count`` cache groups (gx, gy) of ``g x g`` tiles, drawn from the
    seed among those wholly inside the image."""
    side = g * 16
    cand = [(gx, gy) for gy in range(height // side)
            for gx in range(width // side)]
    rng = np.random.default_rng(int(seed) ^ 0xC0FFEE)
    pick = rng.choice(len(cand), size=min(count, len(cand)), replace=False)
    return [cand[i] for i in sorted(pick)]
