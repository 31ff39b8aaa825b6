"""The comparison that decides ``correct``: what the timed path produced,
against :mod:`bench.reference`.

Every number compared is a count of wrong answers, and every limit is 0:
the configurations state exact wing and tip numbers and an exact forest.
"""
from __future__ import annotations

import numpy as np

from . import reference as ref

# arrays of a program forest the comparison reads
FOREST_FIELDS = ("node_level", "parent", "entity_node", "ent_order",
                 "estart", "eend", "node_m", "node_nu", "node_nv")


def entity_map(kind: str, prog_edges: np.ndarray, canon: np.ndarray):
    """Canonical entity of each program entity: a wing entity is matched
    by its (u, v) edge, a tip entity is its vertex id."""
    if kind != "wing":
        return None
    n_v = int(max(canon[:, 1].max(), prog_edges[:, 1].max())) + 1
    key = canon[:, 0] * n_v + canon[:, 1]
    pk = prog_edges[:, 0].astype(np.int64) * n_v + prog_edges[:, 1]
    pos = np.searchsorted(key, pk)
    pos = np.minimum(pos, key.size - 1)
    return np.where(key[pos] == pk, pos, -1)


def node_ids(h: dict, emap, forest: ref.Forest) -> np.ndarray:
    """Canonical node id of each program node (-1: no such node)."""
    order = h["ent_order"] if emap is None else emap[h["ent_order"]]
    lo = np.asarray(h["estart"], np.int64)
    hi = np.asarray(h["eend"], np.int64)
    sub_min = np.array([order[a:b].min() if b > a else -1
                        for a, b in zip(lo, hi)], np.int64)
    return ref.canonical_node_ids(h["node_level"], sub_min, forest)


def compare_job(kind: str, out: dict, canon: np.ndarray,
                forest: ref.Forest) -> dict:
    """Wrong entity numbers and wrong forest entries of one job."""
    emap = entity_map(kind, out["edges"], canon)
    theta = np.asarray(out["theta"], np.int64)
    n_ent = forest.theta.size
    if emap is None:
        emap_e = np.arange(theta.size)
    else:
        emap_e = emap
    ok = (emap_e >= 0) & (emap_e < n_ent)
    theta_wrong = int((~ok).sum()) + abs(theta.size - n_ent)
    theta_wrong += int((theta[ok] != forest.theta[emap_e[ok]]).sum())

    h = out["forest"]
    cid = node_ids(h, emap, forest)
    good = cid >= 0
    wrong = np.zeros(cid.size, bool)
    wrong |= ~good
    par = np.asarray(h["parent"], np.int64)
    ref_par = np.where(good, forest.parent[np.maximum(cid, 0)], -2)
    got_par = np.where(par >= 0, cid[np.maximum(par, 0)], -1)
    wrong |= got_par != ref_par
    size = np.asarray(h["eend"]) - np.asarray(h["estart"])
    for got, want in ((size, forest.node_size), (h["node_m"], forest.node_m),
                      (h["node_nu"], forest.node_nu),
                      (h["node_nv"], forest.node_nv)):
        wrong |= np.asarray(got) != np.where(good, want[np.maximum(cid, 0)],
                                             -1)
    missing = max(forest.n_nodes - int(np.unique(cid[good]).size), 0)
    en = cid[np.asarray(h["entity_node"], np.int64)]
    ent_wrong = int((en[ok] != forest.entity_node[emap_e[ok]]).sum())
    return dict(theta_wrong=theta_wrong,
                forest_wrong=int(wrong.sum()) + missing + ent_wrong)


def reference_forest(kind: str, side: str, n_u: int, n_v: int,
                     edges: np.ndarray, coarse: int = 1) -> ref.Forest:
    """The reference forest of the graph (``coarse`` > 1: the control)."""
    if kind == "wing":
        theta = ref.wing_numbers(n_u, n_v, edges, coarse=coarse)
    else:
        theta = ref.tip_numbers(n_u, n_v, edges, side, coarse=coarse)
    return ref.build_forest(kind, n_u, n_v, edges, theta, side)


def compare_batch(kind: str, side: str, n_u: int, n_v: int,
                  edges: np.ndarray, outs: list) -> list:
    """The wrong counts of every job of the window."""
    canon = ref.canonical_edges(edges)
    forest = reference_forest(kind, side, n_u, n_v, edges)
    return [compare_job(kind, out, canon, forest) for out in outs]


def as_output(forest: ref.Forest, edges: np.ndarray) -> dict:
    """A reference forest in the shape a job reports its result: entity
    numbers plus the forest arrays, subtrees as preorder slices of
    ``ent_order``.  It puts the reference (or its control) in the
    program's place."""
    n = forest.n_nodes
    kids = [[] for _ in range(n)]
    for x in range(1, n):
        kids[forest.parent[x]].append(x)
    members = [[] for _ in range(n)]
    for e, x in enumerate(forest.entity_node):
        members[x].append(e)
    start = np.zeros(n, np.int64)
    order, stack = [], [0]
    while stack:
        x = stack.pop()
        start[x] = len(order)
        order += members[x]
        stack += kids[x][::-1]
    return dict(edges=ref.canonical_edges(edges), theta=forest.theta,
                forest=dict(node_level=forest.node_level,
                            parent=forest.parent,
                            entity_node=forest.entity_node,
                            ent_order=np.asarray(order, np.int64),
                            estart=start, eend=start + forest.node_size,
                            node_m=forest.node_m, node_nu=forest.node_nu,
                            node_nv=forest.node_nv))
