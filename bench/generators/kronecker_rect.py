"""Graph 500's Kronecker generator on a rectangular matrix: U has
``2**scale_u`` ids and V ``2**scale_v`` (scale_v ≤ scale_u), R-MAT's
rectangular reading of the adjacency matrix (each generated edge starts
in U and ends in V).

Each of ``edgefactor * 2**scale_u`` edges draws its quadrant at every
one of ``scale_u`` levels exactly as ``kronecker.py`` does, with the
same two draws per level: U's bit is 1 with probability C + D =
1 - A - B, then V's bit given U's.  V's bit is kept at the first
``scale_v`` levels only; after them each level splits U alone, with
R-MAT's marginal probability for U's bit.  Repeated edges are dropped,
since the peel takes a simple graph.
"""
from __future__ import annotations

import numpy as np


def edges(cfg: dict, cut: float = 1.0) -> tuple:
    """(|U|, |V|, distinct edges) of the configuration; ``cut`` < 1
    takes ``log2(cut)`` levels off ``scale_u`` only."""
    drop = -np.log2(cut)
    if drop != int(drop):
        raise ValueError(f"a Kronecker graph is cut by powers of 2, not {cut}")
    scale_u, scale_v = cfg["scale_u"] - int(drop), cfg["scale_v"]
    if scale_v > scale_u:
        raise ValueError(f"scale_v {scale_v} exceeds scale_u {scale_u}")
    a, b, c = cfg["A"], cfg["B"], cfg["C"]
    rng = np.random.default_rng(cfg["graph_seed"])
    m = cfg["edgefactor"] * 2 ** scale_u
    u = np.zeros(m, np.int64)
    v = np.zeros(m, np.int64)
    for level in range(scale_u):
        u_bit = rng.random(m) > a + b
        v_bit = rng.random(m) > np.where(u_bit, c / (1 - a - b), a / (a + b))
        u += u_bit.astype(np.int64) << level
        if level < scale_v:
            v += v_bit.astype(np.int64) << level
    key = np.unique(u << scale_v | v)
    return 2 ** scale_u, 2 ** scale_v, np.stack(
        [key >> scale_v, key & (2 ** scale_v - 1)], axis=1)
