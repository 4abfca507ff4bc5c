"""Vectorised integer hashing for features derived from URL ids."""
from __future__ import annotations

import numpy as np


def mix64(x: np.ndarray) -> np.ndarray:
    """The splitmix64 finaliser on uint64 arrays (wrapping arithmetic)."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))
