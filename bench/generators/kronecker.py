"""Graph 500's Kronecker generator, its edge list read as a bipartite
graph: the start of each generated edge is a vertex of U, its end a
vertex of V (the rectangular reading of R-MAT's adjacency matrix).

Each of ``edgefactor * 2**scale`` edges picks, at every one of ``scale``
levels, one quadrant of the adjacency matrix with probabilities A, B,
C and 1 - A - B - C, as the specification's reference code draws them.
Repeated edges are dropped, since the peel takes a simple graph.
"""
from __future__ import annotations

import numpy as np


def edges(cfg: dict, cut: float = 1.0) -> tuple:
    """(|U|, |V|, distinct edges) of the configuration; ``cut`` < 1
    takes ``log2(cut)`` levels off its scale."""
    drop = -np.log2(cut)
    if drop != int(drop):
        raise ValueError(f"a Kronecker graph is cut by powers of 2, not {cut}")
    scale = cfg["scale"] - int(drop)
    a, b, c = cfg["A"], cfg["B"], cfg["C"]
    rng = np.random.default_rng(cfg["graph_seed"])
    m = cfg["edgefactor"] * 2 ** scale
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for level in range(scale):
        u_bit = rng.random(m) > a + b
        v_bit = rng.random(m) > np.where(u_bit, c / (1 - a - b), a / (a + b))
        u += u_bit.astype(np.int64) << level
        v += v_bit.astype(np.int64) << level
    n = 2 ** scale
    return n, n, np.unique(np.stack([u, v], axis=1), axis=0)
