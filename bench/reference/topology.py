"""Mixing matrices of the topologies the traffic files name, written out
plainly: symmetric, doubly stochastic, nonnegative."""
from __future__ import annotations

import numpy as np


def mixing_matrix(topology: str, n: int) -> np.ndarray:
    """``ring``: each client keeps 1/3 and takes 1/3 from each neighbour
    (n = 2 averages the pair)."""
    if topology != "ring":
        raise ValueError(f"the reference knows no topology {topology!r}")
    if n == 1:
        return np.ones((1, 1))
    if n == 2:
        return np.full((2, 2), 0.5)
    w = np.zeros((n, n))
    for i in range(n):
        for j in (i - 1, i, i + 1):
            w[i, j % n] += 1.0 / 3.0
    return w
