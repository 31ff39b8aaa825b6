"""Graphs of a configuration, made from seeds.

A configuration fixes the graph's shape: its generator
(``bench/generators/<generator>.py``, found by name), the generator's
parameters and the structure seed (``graph_seed``), so that every run
decomposes the same deployment.  A run's ``--seed`` relabels both
vertex sets by a random permutation and shuffles the edge order: each
run's input differs, and the work it takes does not.
"""
from __future__ import annotations

import importlib

import numpy as np


def structure(cfg: dict, cut: float = 1.0) -> tuple:
    """(|U|, |V|, edges) of the configuration's graph, before
    relabelling; ``cut`` < 1 is a smaller graph of the same shape."""
    gen = importlib.import_module(f"bench.generators.{cfg['generator']}")
    return gen.edges(cfg, cut)


def relabel(edges: np.ndarray, n_u: int, n_v: int, seed: int) -> np.ndarray:
    """The same graph with vertex ids permuted and rows shuffled."""
    rng = np.random.default_rng(seed % 2 ** 64)
    pu = rng.permutation(n_u)
    pv = rng.permutation(n_v)
    e = np.stack([pu[edges[:, 0]], pv[edges[:, 1]]], axis=1)
    return e[rng.permutation(e.shape[0])]
