"""Stratified exponential arrivals: each block of ``n`` gaps holds the
exponential distribution's values at the quantiles ``(i + 0.5) / n``, in
an order drawn from ``rng``.  Every block spans the same time, so
bursts longer than a block do not occur; the mean rate is Poisson's."""

import math

import numpy as np


def gaps(rng: np.random.Generator, n: int, mean_s: float, params: dict):
    """``n`` gaps between sessions, ``mean_s`` seconds on average."""
    values = [-mean_s * math.log1p(-(i + 0.5) / n) for i in range(n)]
    rng.shuffle(values)
    return values
