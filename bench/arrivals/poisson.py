"""Poisson arrivals: independent exponential gaps."""

import numpy as np


def gaps(rng: np.random.Generator, n: int, mean_s: float, params: dict):
    """``n`` gaps between sessions, ``mean_s`` seconds on average."""
    return rng.exponential(mean_s, n).tolist()
