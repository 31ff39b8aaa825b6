"""Plain reference for the benchmark's correctness check.

Independent of the system under test: it imports nothing of ``repro``
and takes nothing the system made.  From an edge list alone it computes

* wing numbers (per edge) and tip numbers (per vertex of one side) by
  level-synchronous bottom-up peeling: at level k every alive entity
  whose butterfly support is at most k dies with number k, supports are
  updated, and k rises to the least alive support once nothing at or
  below k is left;
* the dense-subgraph forest of those numbers: for every level k >= 1 the
  butterfly-connected components of the entities numbered >= k, one node
  per component that holds an entity numbered exactly k, each node's
  parent being the deepest lower-level node that contains it, under a
  level-0 root;
* the answers of the five forest queries.

Entities are numbered canonically: a wing entity is the index of its
edge in the lexicographically sorted list of distinct ``(u, v)`` edges,
a tip entity is its vertex id.  Nodes are numbered canonically too: the
root is 0, then nodes by level, and within a level by the least entity
of their component.  :func:`canonical_node_ids` maps a forest numbered
any other way onto this numbering.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "Forest",
    "canonical_edges",
    "wing_numbers",
    "tip_numbers",
    "build_forest",
    "answer",
    "canonical_node_ids",
]

_INF = np.iinfo(np.int64).max


def canonical_edges(edges: np.ndarray) -> np.ndarray:
    """Distinct edges, sorted by (u, v): the reference's wing entities."""
    return np.unique(np.asarray(edges, dtype=np.int64).reshape(-1, 2), axis=0)


def _ranges(off: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Concatenation of ``arange(off[i], off[i + 1])`` for every i in idx."""
    lo = off[idx]
    n = off[idx + 1] - lo
    tot = int(n.sum())
    if tot == 0:
        return np.zeros(0, np.int64)
    starts = np.repeat(lo - (np.cumsum(n) - n), n)
    return starts + np.arange(tot, dtype=np.int64)


def _csr(keys: np.ndarray, n: int):
    """Order of ``keys`` grouped by key, and the group offsets."""
    order = np.argsort(keys, kind="stable")
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(keys, minlength=n), out=off[1:])
    return order, off


def _wedges(n_u: int, n_v: int, edges: np.ndarray):
    """Every wedge u1 - v - u2 (u1 < u2) as (edge of u1, edge of u2, pair),
    pairs numbered over the distinct (u1, u2)."""
    m = edges.shape[0]
    by_v = np.lexsort((edges[:, 0], edges[:, 1]))
    v = edges[by_v, 1]
    off = np.zeros(n_v + 1, np.int64)
    np.cumsum(np.bincount(v, minlength=n_v), out=off[1:])
    pos = np.arange(m, dtype=np.int64)
    later = off[v + 1] - pos - 1                 # positions after p, same v
    p1 = np.repeat(pos, later)
    first = np.cumsum(later) - later
    p2 = p1 + 1 + (np.arange(p1.size, dtype=np.int64)
                   - np.repeat(first, later))
    e1, e2 = by_v[p1], by_v[p2]
    key = edges[e1, 0] * n_u + edges[e2, 0]
    pair_key, pair = np.unique(key, return_inverse=True)
    return e1, e2, pair.reshape(-1), pair_key // n_u, pair_key % n_u


def wing_numbers(n_u: int, n_v: int, edges: np.ndarray,
                 coarse: int = 1) -> np.ndarray:
    """Wing number of every edge of ``canonical_edges(edges)``.

    ``coarse`` > 1 is the control, which breaks exactness: each round
    peels every support below k + coarse at level k, as a coarse range
    partition would without its fine pass."""
    e = canonical_edges(edges)
    m = e.shape[0]
    e1, e2, wp, pa, _ = _wedges(n_u, n_v, e)
    nw, npairs = e1.size, pa.size
    c = np.bincount(wp, minlength=npairs).astype(np.int64)   # alive wedges
    contrib = (c[wp] - 1).astype(np.float64)
    sup = (np.bincount(e1, contrib, m) + np.bincount(e2, contrib, m)
           ).astype(np.int64)
    ew, ew_off = _csr(np.concatenate([e1, e2]), m)
    ew = ew % max(nw, 1)                         # incidence -> wedge id
    pw, pw_off = _csr(wp, npairs)
    alive_e = np.ones(m, bool)
    alive_w = np.ones(nw, bool)
    theta = np.zeros(m, np.int64)
    supm = sup.copy()                            # _INF once dead
    k = 0
    left = m
    while left:
        k = max(k, int(supm.min()))
        dying = np.flatnonzero(supm <= k + (coarse - 1))
        theta[dying] = k
        supm[dying] = _INF
        alive_e[dying] = False
        left -= dying.size
        ws = ew[_ranges(ew_off, dying)]
        ws = np.unique(ws[alive_w[ws]])
        if ws.size == 0:
            continue
        alive_w[ws] = False
        loss = np.zeros(m, np.float64)
        # a surviving edge of a dying wedge loses every butterfly that
        # wedge formed with the other alive wedges of its pair
        lost = (c[wp[ws]] - 1).astype(np.float64)
        for ee in (e1[ws], e2[ws]):
            ok = alive_e[ee]
            loss += np.bincount(ee[ok], lost[ok], m)
        ps, d = np.unique(wp[ws], return_counts=True)
        c[ps] -= d
        # each edge of a surviving wedge of those pairs loses one
        # butterfly per dying wedge of the pair
        dp = np.zeros(npairs, np.float64)
        dp[ps] = d
        sw = pw[_ranges(pw_off, ps)]
        sw = sw[alive_w[sw]]
        loss += (np.bincount(e1[sw], dp[wp[sw]], m)
                 + np.bincount(e2[sw], dp[wp[sw]], m))
        alive_idx = np.flatnonzero(alive_e)
        supm[alive_idx] -= loss[alive_idx].astype(np.int64)
    return theta


def _tip_pairs(n_u: int, n_v: int, edges: np.ndarray, side: str):
    e = canonical_edges(edges)
    if side == "v":
        e = canonical_edges(e[:, ::-1])
        n_u, n_v = n_v, n_u
    _, _, wp, pa, pb = _wedges(n_u, n_v, e)
    c = np.bincount(wp, minlength=pa.size).astype(np.int64)
    return n_u, pa, pb, c


def tip_numbers(n_u: int, n_v: int, edges: np.ndarray,
                side: str = "u", coarse: int = 1) -> np.ndarray:
    """Tip number of every vertex of ``side`` ("u" or "v"); ``coarse``
    as in :func:`wing_numbers`."""
    n, pa, pb, c = _tip_pairs(n_u, n_v, edges, side)
    bf = c * (c - 1) // 2
    keep = bf > 0
    pa, pb, bf = pa[keep], pb[keep], bf[keep]
    fbf = bf.astype(np.float64)
    sup = (np.bincount(pa, fbf, n) + np.bincount(pb, fbf, n)).astype(np.int64)
    ends = np.concatenate([pa, pb])
    partner = np.concatenate([pb, pa])
    order, off = _csr(ends, n)
    partner, w = partner[order], np.concatenate([fbf, fbf])[order]
    alive = np.ones(n, bool)
    theta = np.zeros(n, np.int64)
    supm = sup.copy()
    k = 0
    left = n
    while left:
        k = max(k, int(supm.min()))
        dying = np.flatnonzero(supm <= k + (coarse - 1))
        theta[dying] = k
        supm[dying] = _INF
        alive[dying] = False
        left -= dying.size
        inc = _ranges(off, dying)
        q = partner[inc]
        ok = alive[q]
        loss = np.bincount(q[ok], w[inc][ok], n).astype(np.int64)
        hit = np.flatnonzero(loss)
        supm[hit] -= loss[hit]
    return theta


# ------------------------------------------------------------------ forest
@dataclasses.dataclass
class Forest:
    """The reference forest in canonical numbering."""

    theta: np.ndarray         # (n_entities,) entity numbers
    node_level: np.ndarray    # (n_nodes,)
    node_rep: np.ndarray      # (n_nodes,) least entity of the component
    parent: np.ndarray        # (n_nodes,) -1 at the root
    entity_node: np.ndarray   # (n_entities,) node of each entity
    node_size: np.ndarray     # (n_nodes,) entities in the subtree
    node_m: np.ndarray        # (n_nodes,) edges of the induced subgraph
    node_nu: np.ndarray       # (n_nodes,) U vertices it spans
    node_nv: np.ndarray       # (n_nodes,) V vertices it spans

    @property
    def n_nodes(self) -> int:
        """Number of nodes, the root included."""
        return int(self.node_level.size)

    def depth(self) -> np.ndarray:
        """Depth of every node below the root."""
        d = np.zeros(self.n_nodes, np.int64)
        for x in range(1, self.n_nodes):         # parents come first
            d[x] = d[self.parent[x]] + 1
        return d


class _UnionFind:
    """Union-find whose root is always the least member; merging two sets
    merges their lists of nodes still waiting for a parent."""

    def __init__(self, n: int):
        self.up = list(range(n))
        self.waiting: dict = {}

    def find(self, x: int) -> int:
        """Root (least member) of x's set."""
        up = self.up
        r = x
        while up[r] != r:
            r = up[r]
        while up[x] != r:
            up[x], x = r, up[x]
        return r

    def union(self, a: int, b: int) -> None:
        """Merge the sets of a and b."""
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return
        if rb < ra:
            ra, rb = rb, ra
        self.up[rb] = ra
        moved = self.waiting.pop(rb, None)
        if moved:
            self.waiting.setdefault(ra, []).extend(moved)


def _wing_links(n_u, n_v, e, theta):
    """(level, a, b): edges a and b are butterfly-connected at every level
    up to ``level``."""
    e1, e2, wp, pa, _ = _wedges(n_u, n_v, e)
    lw = np.minimum(theta[e1], theta[e2])        # level a wedge lives to
    order = np.lexsort((-lw, wp))                # by pair, level descending
    wp_s, lw_s = wp[order], lw[order]
    first = np.ones(order.size, bool)
    first[1:] = wp_s[1:] != wp_s[:-1]
    start = np.flatnonzero(first)
    size = np.diff(np.append(start, order.size))
    grp = np.repeat(np.arange(start.size), size)
    second = np.where(size >= 2, lw_s[np.minimum(start + 1, order.size - 1)],
                      -1)                        # a pair connects up to here
    anchor = e1[order][start]                    # edge of its top wedge
    lvl = np.minimum(lw_s, second[grp])
    ok = lvl >= 1
    a = anchor[grp][ok]
    lvl = lvl[ok]
    o = order[ok]
    return (np.concatenate([lvl, lvl]), np.concatenate([a, a]),
            np.concatenate([e1[o], e2[o]]))


def _tip_links(n_u, n_v, edges, side, theta):
    _, pa, pb, c = _tip_pairs(n_u, n_v, edges, side)
    keep = c >= 2
    pa, pb = pa[keep], pb[keep]
    return np.minimum(theta[pa], theta[pb]), pa, pb


def build_forest(kind: str, n_u: int, n_v: int, edges: np.ndarray,
                 theta: np.ndarray, side: str = "u") -> Forest:
    """The forest of ``theta`` (canonical entity numbering) on the graph."""
    e = canonical_edges(edges)
    theta = np.asarray(theta, np.int64)
    n_ent = theta.size
    if kind == "wing":
        lvl, a, b = _wing_links(n_u, n_v, e, theta)
    else:
        lvl, a, b = _tip_links(n_u, n_v, e, side, theta)
    order = np.argsort(-lvl, kind="stable")
    lvl, a, b = lvl[order].tolist(), a[order].tolist(), b[order].tolist()
    uf = _UnionFind(n_ent)
    by_level = np.argsort(-theta, kind="stable")
    th_sorted = theta[by_level]
    levels = np.unique(theta[theta > 0])[::-1]
    keys, parent_key = [], {}
    ent_key = np.zeros((n_ent, 2), np.int64)     # (level, rep); level 0 = root
    j = 0
    for k in levels.tolist():
        while j < len(lvl) and lvl[j] >= k:
            uf.union(a[j], b[j])
            j += 1
        lo, hi = np.searchsorted(-th_sorted, [-k, -k + 1])
        own = by_level[lo:hi].tolist()
        roots = [uf.find(x) for x in own]
        ent_key[own, 0] = k
        ent_key[own, 1] = roots
        for r in sorted(set(roots)):
            key = (k, r)
            keys.append(key)
            for child in uf.waiting.pop(r, ()):
                parent_key[child] = key
            uf.waiting[r] = [key]
    keys.sort()
    node_of = {key: i + 1 for i, key in enumerate(keys)}
    n_nodes = len(keys) + 1
    parent = np.full(n_nodes, 0, np.int64)
    parent[0] = -1
    for key, pk in parent_key.items():
        parent[node_of[key]] = node_of[pk]
    node_level = np.array([0] + [k for k, _ in keys], np.int64)
    node_rep = np.array([-1] + [r for _, r in keys], np.int64)
    entity_node = np.zeros(n_ent, np.int64)
    has = ent_key[:, 0] > 0
    entity_node[has] = [node_of[(int(k), int(r))] for k, r in ent_key[has]]
    stats = _node_stats(kind, n_u, n_v, e, side, parent, entity_node)
    return Forest(theta, node_level, node_rep, parent, entity_node, *stats)


def _node_stats(kind, n_u, n_v, e, side, parent, entity_node):
    """Subtree size, and edges / U / V vertices of each node's subgraph."""
    n_nodes = parent.size
    # every (ancestor-or-self node, entity) pair
    node_l, ent_l = [], []
    cur = entity_node.copy()
    ent = np.arange(entity_node.size)
    while cur.size:
        node_l.append(cur)
        ent_l.append(ent)
        keep = cur > 0
        cur, ent = parent[cur[keep]], ent[keep]
    node = np.concatenate(node_l)
    ent = np.concatenate(ent_l)
    size = np.bincount(node, minlength=n_nodes)

    def distinct(nd, vals, n_vals):
        """Distinct values per node."""
        key = np.unique(nd * n_vals + vals)
        return np.bincount(key // n_vals, minlength=n_nodes)

    if kind == "wing":
        return (size, size.copy(), distinct(node, e[ent, 0], n_u),
                distinct(node, e[ent, 1], n_v))
    if side == "v":
        e = canonical_edges(e[:, ::-1])
        n_u, n_v = n_v, n_u
    deg = np.bincount(e[:, 0], minlength=n_u)
    m = np.bincount(node, deg[ent], minlength=n_nodes).astype(np.int64)
    off = np.zeros(n_u + 1, np.int64)
    np.cumsum(deg, out=off[1:])
    inc = _ranges(off, ent)
    nd = np.repeat(node, deg[ent])
    return size, m, size.copy(), distinct(nd, e[inc, 1], n_v)


# ------------------------------------------------------------------ queries
OPS = ("max_k", "node_of", "lca_node", "lca_level", "subtree_size")


def answer(f: Forest, op: np.ndarray, a: np.ndarray, b: np.ndarray,
           depth: np.ndarray = None) -> np.ndarray:
    """Answers of queries ``(op, a, b)``; ``op`` indexes :data:`OPS`, ``a``
    is a node for ``subtree_size`` and an entity otherwise, ``b`` an
    entity.  Node answers are canonical node ids."""
    op, a, b = (np.asarray(x, np.int64) for x in (op, a, b))
    if depth is None:
        depth = f.depth()
    out = np.full(op.size, -1, np.int64)
    ent = op != OPS.index("subtree_size")
    aa = np.where(ent, a, 0)
    x = f.entity_node[aa]
    y = f.entity_node[b]
    while True:                                  # walk up to equal depth
        dx, dy = depth[x], depth[y]
        if not (dx != dy).any():
            break
        x = np.where(dx > dy, f.parent[x], x)
        y = np.where(dy > dx, f.parent[y], y)
    while (x != y).any():
        ne = x != y
        x = np.where(ne, f.parent[x], x)
        y = np.where(ne, f.parent[y], y)
    got = {
        "max_k": f.theta[aa],
        "node_of": f.entity_node[aa],
        "lca_node": x,
        "lca_level": f.node_level[x],
        "subtree_size": f.node_size[np.where(ent, 0, a)],
    }
    for i, name in enumerate(OPS):
        sel = op == i
        out[sel] = got[name][sel]
    return out


def canonical_node_ids(node_level: np.ndarray, subtree_min: np.ndarray,
                       ref: Forest) -> np.ndarray:
    """Canonical id of each node of another forest, given each node's
    level and the least canonical entity under it; -1 where the reference
    has no such node."""
    lookup = {(int(k), int(r)): i for i, (k, r) in
              enumerate(zip(ref.node_level, ref.node_rep)) if k > 0}
    return np.array([0 if k == 0 else lookup.get((int(k), int(r)), -1)
                     for k, r in zip(node_level, subtree_min)], np.int64)
