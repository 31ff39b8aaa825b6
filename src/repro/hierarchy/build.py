"""θ → hierarchy forest: the nested dense-subgraph DAG (Sarıyüce's
k-wing / k-tip nuclei) materialized from peel output.

For every distinct level k ≥ 1 the k-subgraph is the set of entities
with θ ≥ k (edges for wing, one-side vertices for tip); its
*butterfly-connected* components are the hierarchy nodes.  Components
only split as k grows, so the nodes form a forest under containment —
we root it with a level-0 node holding the whole graph, making every
query an ancestor problem.

Connectivity is butterfly connectivity, stated on the wedge machinery of
``core.csr``: two entities are connected at level k iff a chain of
butterflies of the k-subgraph joins them.  A butterfly is two wedges of
one U-endpoint *pair*, so the connectivity graph is the bipartite
incidence entity ↔ pair, restricted to pairs holding ≥ 2 alive wedges.
Components are computed levels-batched by min-label propagation over
that incidence — one ``lax.while_loop`` per block of ``level_block``
levels (a single compiled shape; memory stays O(level_block × wedges)
however many θ levels the graph has), each iteration two
``segment_min`` hops vmapped across the block's levels; no Python
per-edge loops anywhere on the device path.

Nodes are *collapsed*: a node exists at level k only if some entity has
θ == k in it (a component whose members all survive to the next level
is the same subgraph there — representing it twice would add chain
nodes that answer no query).  Each entity therefore belongs to exactly
one node (its component at level θ), nodes are created level-ascending
(``parent[x] < x`` always), and member lists partition the entity set.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import csr
from repro.core.graph import BipartiteGraph
from repro.core.peel import PeelResult

__all__ = ["Hierarchy", "build_hierarchy"]

_BIG = jnp.iinfo(jnp.int32).max


# =====================================================================
# Packed forest container
# =====================================================================
@dataclasses.dataclass
class Hierarchy:
    """CSR-packed hierarchy forest (host numpy; see :mod:`query` for the
    device-resident view).

    Node 0 is the level-0 root holding the whole graph; its *own*
    members are the butterfly-free entities (θ = 0).  ``ent_order``
    sorts entities by the preorder stamp of their node, so every node's
    subtree entity set is the contiguous slice
    ``ent_order[estart[x]:eend[x]]`` — the O(1) backbone of
    ``subgraph_at`` and the density stats.
    """

    kind: str                 # "wing" | "tip"
    n_entities: int
    theta: np.ndarray         # (n_entities,) int64 — peel numbers
    node_level: np.ndarray    # (n_nodes,) int64 — k of each node
    parent: np.ndarray        # (n_nodes,) int32 — parent id, -1 at root
    entity_node: np.ndarray   # (n_entities,) int32 — deepest node per entity
    member_off: np.ndarray    # (n_nodes+1,) int64 — own-member CSR
    member_ids: np.ndarray    # (n_entities,) int32
    child_off: np.ndarray     # (n_nodes+1,) int64 — children CSR
    child_ids: np.ndarray     # (n_nodes-1,) int32
    tin: np.ndarray           # (n_nodes,) int32 — preorder stamp
    tout: np.ndarray          # (n_nodes,) int32 — subtree = [tin, tout)
    ent_order: np.ndarray     # (n_entities,) int32 — entities by node tin
    estart: np.ndarray        # (n_nodes,) int64 — subtree slice start
    eend: np.ndarray          # (n_nodes,) int64 — subtree slice end
    node_m: np.ndarray        # (n_nodes,) int64 — induced edge count
    node_nu: np.ndarray       # (n_nodes,) int64 — induced |U| span
    node_nv: np.ndarray       # (n_nodes,) int64 — induced |V| span
    density: np.ndarray       # (n_nodes,) f64 — m / (nu · nv)
    meta: Dict                # provenance: engine tags, PeelStats, ...

    @property
    def n_nodes(self) -> int:
        """Number of forest nodes (dense subgraphs) after chain collapse."""
        return int(self.node_level.shape[0])

    @property
    def levels(self) -> np.ndarray:
        """Distinct θ levels ≥ 1 present in the forest, ascending."""
        lv = np.unique(self.node_level)
        return lv[lv > 0]

    def subtree_entities(self, node: int) -> np.ndarray:
        """All entities of the node's subgraph (own + descendants)."""
        return self.ent_order[int(self.estart[node]):int(self.eend[node])]

    def members(self, node: int) -> np.ndarray:
        """Own members only (entities with θ == node_level[node])."""
        return self.member_ids[
            int(self.member_off[node]):int(self.member_off[node + 1])
        ]

    def children(self, node: int) -> np.ndarray:
        """Child node ids (denser subgraphs nested inside this one)."""
        return self.child_ids[
            int(self.child_off[node]):int(self.child_off[node + 1])
        ]


# =====================================================================
# Batched connected components (device): min-label propagation
# =====================================================================
@partial(jax.jit, static_argnames=("n_entities", "n_groups"))
def _label_components(
    alive_inc: jax.Array,   # (L, n_inc) bool — incidence alive per level
    inc_e: jax.Array,       # (n_inc,) int32 — entity endpoint
    inc_g: jax.Array,       # (n_inc,) int32 — group (pair) endpoint
    lab0: jax.Array,        # (L, n_entities) int32 — entity id | _BIG dead
    n_entities: int,
    n_groups: int,
):
    """Connected components of L level-subgraphs in ONE ``while_loop``.

    Each iteration is two segment_min hops over the entity↔group
    incidence (entity labels → group minima → back), vmapped across
    levels; the loop runs until no label moves in ANY level.  The fixed
    point labels every entity with the minimum entity id of its
    component (``_BIG`` for dead entities), which doubles as a canonical
    component representative.
    """

    def one(lab, alive):
        up = jnp.where(alive, lab[inc_e], _BIG)
        gmin = jax.ops.segment_min(up, inc_g, num_segments=max(n_groups, 1))
        down = jnp.where(alive, gmin[inc_g], _BIG)
        return jnp.minimum(
            lab, jax.ops.segment_min(down, inc_e, num_segments=n_entities)
        )

    def body(state):
        lab, _ = state
        new = jax.vmap(one)(lab, alive_inc)
        return new, jnp.any(new != lab)

    lab, _ = jax.lax.while_loop(
        lambda s: s[1], body, (lab0, jnp.bool_(True))
    )
    return lab


@partial(jax.jit, static_argnames=("n_pairs",))
def _wing_conn_incidence(
    alive_e: jax.Array,     # (L, m) bool
    we1: jax.Array,
    we2: jax.Array,
    wp: jax.Array,
    n_pairs: int,
):
    """Per-level connective-wedge mask: wedge alive (both edges in the
    level subgraph) AND its pair holds ≥ 2 alive wedges — the pair then
    witnesses a butterfly joining every edge incident to it."""

    def one(al):
        alive_w = al[we1] & al[we2]
        W = jax.ops.segment_sum(
            alive_w.astype(jnp.int32), wp, num_segments=max(n_pairs, 1)
        )
        return alive_w & (W[wp] >= 2)

    return jax.vmap(one)(alive_e)


def _pad_block(x: np.ndarray, block: int) -> np.ndarray:
    """Pad the level axis up to ``block`` rows with all-dead levels
    (inert in the propagation) so every chunk shares one compiled
    shape."""
    pad = block - x.shape[0]
    if pad == 0:
        return x
    fill = np.zeros((pad,) + x.shape[1:], dtype=x.dtype)
    return np.concatenate([x, fill], axis=0)


def _component_labels_per_level(
    gg: BipartiteGraph,
    theta: np.ndarray,
    levels: np.ndarray,
    kind: str,
    level_block: int = 32,
    pairs: Optional[csr.TipPairs] = None,
) -> np.ndarray:
    """(L, n_entities) int64 component labels, _BIG-marked where dead.

    Tip connectivity reads the U pairs sharing ≥ 2 neighbours: those of
    ``pairs`` (the decomposition's own list) when given, else of
    ``csr.tip_pairs`` — never the wedge list.

    Levels are processed in fixed chunks of ``level_block`` (all-dead
    padded to one compiled shape): the propagation state is
    O(level_block × incidences), NOT O(L × incidences) — a graph with
    thousands of distinct θ levels must not need thousands of wedge-list
    copies resident at once.  Chunks are independent (each level's
    fixpoint is its own), so this is a pure memory/dispatch trade."""
    n_ent = gg.m if kind == "wing" else gg.n_u
    L = levels.size
    if L == 0 or n_ent == 0:
        return np.zeros((0, n_ent), dtype=np.int64)

    with obs.span("hierarchy.wedges", cat="hierarchy"):
        if kind == "wing":
            wed = csr.build_wedges(gg)
            we1 = jnp.asarray(wed.wedge_e1)
            we2 = jnp.asarray(wed.wedge_e2)
            wp = jnp.asarray(wed.wedge_pair)
            inc_e = jnp.concatenate([we1, we2])
            inc_g = jnp.concatenate([wp, wp])
            n_groups = wed.n_pairs
        else:
            # pairs with ≥ 2 common neighbours share a butterfly (V is
            # never peeled, so W0 is the pair's count at every level)
            if pairs is None or pairs.n_u != n_ent:
                pairs = csr.tip_pairs(gg)
            conn_p = pairs.W0 >= 2
            pa = pairs.pair_a[conn_p].astype(np.int32)
            pb = pairs.pair_b[conn_p].astype(np.int32)
            pid = np.arange(pa.size, dtype=np.int32)
            inc_e = jnp.asarray(np.concatenate([pa, pb]))
            inc_g = jnp.asarray(np.concatenate([pid, pid]))
            n_groups = int(pa.size)

    ids = jnp.arange(n_ent, dtype=jnp.int32)[None, :]
    out = np.empty((L, n_ent), dtype=np.int64)
    for lo in range(0, L, level_block):
        chunk = levels[lo:lo + level_block]
        n = chunk.size
        alive = _pad_block(theta[None, :] >= chunk[:, None], level_block)
        alive_j = jnp.asarray(alive)
        if kind == "wing":
            conn = _wing_conn_incidence(alive_j, we1, we2, wp, n_groups)
            alive_inc = jnp.concatenate([conn, conn], axis=1)
        else:
            ap = alive[:, pa] & alive[:, pb]
            alive_inc = jnp.asarray(np.concatenate([ap, ap], axis=1))
        lab0 = jnp.where(alive_j, ids, _BIG)
        lab = _label_components(
            alive_inc, inc_e, inc_g, lab0, n_ent, n_groups
        )
        out[lo:lo + n] = np.asarray(lab[:n]).astype(np.int64)
    return out


# =====================================================================
# Host assembly: labels → packed forest
# =====================================================================
def _dfs_order(n_nodes: int, child_off, child_ids):
    """Preorder stamps (tin, tout) — iterative, root = node 0."""
    tin = np.zeros(n_nodes, dtype=np.int32)
    tout = np.zeros(n_nodes, dtype=np.int32)
    t = 0
    stack = [(0, False)]
    while stack:
        x, closing = stack.pop()
        if closing:
            tout[x] = t
            continue
        tin[x] = t
        t += 1
        stack.append((x, True))
        kids = child_ids[child_off[x]:child_off[x + 1]]
        for c in kids[::-1]:
            stack.append((int(c), False))
    return tin, tout


def _lca(a: np.ndarray, b: np.ndarray, parent, tin, tout) -> np.ndarray:
    """Lowest common ancestor of each node pair (a[i], b[i]), by binary
    lifting over the preorder stamps (x is an ancestor of y iff
    tin[x] ≤ tin[y] < tout[x])."""
    up = np.where(parent < 0, 0, parent).astype(np.int64)
    jumps = [up]
    while True:                     # 2^j-th ancestors until all hit the root
        nxt = jumps[-1][jumps[-1]]
        if np.array_equal(nxt, jumps[-1]):
            break
        jumps.append(nxt)

    def above(x, y):
        return (tin[x] <= tin[y]) & (tin[y] < tout[x])

    x = a.copy()
    for j in reversed(jumps):       # climb to just below the common part
        y = j[x]
        x = np.where(above(y, b), x, y)
    return np.where(above(a, b), a, up[x])


def _distinct_per_subtree(item_node: np.ndarray, colour: np.ndarray,
                          subtree) -> np.ndarray:
    """Distinct colours among the items of every node's subtree, for
    items placed at ``item_node``: per colour, +1 at each distinct node
    holding it and −1 at the LCA of each node and the next in preorder,
    then subtree sums over the preorder, O(items · log depth).
    ``subtree`` is (parent, tin, tout)."""
    parent, tin, tout = subtree
    n_nodes = parent.size
    out = np.zeros(n_nodes, dtype=np.int64)
    if item_node.size == 0:
        return out
    at_tin = np.argsort(tin)
    key = np.unique(colour.astype(np.int64) * n_nodes + tin[item_node])
    col, node = key // n_nodes, at_tin[key % n_nodes]
    val = np.bincount(node, minlength=n_nodes).astype(np.int64)
    same = col[1:] == col[:-1]
    lca = _lca(node[:-1][same], node[1:][same], parent, tin, tout)
    val -= np.bincount(lca, minlength=n_nodes)
    csum = np.zeros(n_nodes + 1, dtype=np.int64)
    np.cumsum(val[at_tin], out=csum[1:])
    return csum[tout] - csum[tin]


def build_hierarchy(
    g: BipartiteGraph,
    result: Union[PeelResult, np.ndarray],
    kind: str = "wing",
    side: str = "u",
    meta: Optional[Dict] = None,
    level_block: int = 32,
) -> Hierarchy:
    """Construct the k-wing / k-tip hierarchy forest from peel output.

    Traced under a ``hierarchy.build`` span when the obs layer is
    enabled: ``hierarchy.labels`` (the device label program, with the
    wedge rebuild and its uploads as ``hierarchy.wedges``) and
    ``hierarchy.assemble`` (the host assembly, with the induced-subgraph
    stats, vectorized over the edges, as ``hierarchy.node_stats``).
    A tip build reuses the decomposition's pair list
    (``PeelResult.pairs``) for its connectivity; θ levels are compared
    in int64 on the host, so 64-bit tip numbers need nothing else.

    ``result`` is a :class:`~repro.core.peel.PeelResult` from ANY engine
    (``dense`` / ``beindex`` / ``csr`` — their θ are bit-identical, so
    so are the forests) or a raw θ array.  For ``kind="tip"`` pass the
    same ``side`` the decomposition peeled; entities are that side's
    vertices (the graph is transposed internally for ``side="v"``,
    mirroring :func:`~repro.core.peel.tip_decomposition`).

    ``level_block`` caps how many levels' component labelings are
    device-resident at once (memory = O(level_block × wedges)); the
    forest is identical for any value ≥ 1.
    """
    with obs.span("hierarchy.build", cat="hierarchy", kind=kind):
        return _build_hierarchy_impl(
            g, result, kind, side, meta, level_block)


def _build_hierarchy_impl(g, result, kind, side, meta, level_block):
    if kind not in ("wing", "tip"):
        raise ValueError(kind)
    gg = g if (kind == "wing" or side == "u") else g.transpose()
    pairs = None
    if isinstance(result, PeelResult):
        theta = np.asarray(result.theta, dtype=np.int64)
        prov = result.provenance()
        if kind == "tip":
            pairs = result.pairs
    else:
        theta = np.asarray(result, dtype=np.int64)
        prov = {}
    n_ent = gg.m if kind == "wing" else gg.n_u
    if theta.shape != (n_ent,):
        raise ValueError(
            f"theta has shape {theta.shape}, expected ({n_ent},) for "
            f"kind={kind!r}"
        )

    levels = np.unique(theta[theta > 0])
    with obs.span("hierarchy.labels", cat="hierarchy") as sp:
        if sp is not None:
            sp.update(levels=int(levels.size))
        labels = _component_labels_per_level(
            gg, theta, levels, kind, level_block=level_block, pairs=pairs
        )
    with obs.span("hierarchy.assemble", cat="hierarchy"):
        return _assemble_from_labels(
            gg, theta, levels, labels, kind, side, prov, meta)


def _assemble_from_labels(
    gg: BipartiteGraph,
    theta: np.ndarray,
    levels: np.ndarray,
    labels: np.ndarray,
    kind: str,
    side: str,
    prov: Dict,
    meta: Optional[Dict],
) -> Hierarchy:
    """Deterministic host assembly: per-level component labels → the
    packed forest.  Split out of :func:`_build_hierarchy_impl` so the
    streaming repair path (:mod:`repro.hierarchy.repair`) can feed it a
    label matrix where only the dirty levels were recomputed — the
    assembly is a pure function of ``(gg, theta, levels, labels)``, so
    identical inputs give a bit-identical forest however the labels were
    obtained."""
    n_ent = gg.m if kind == "wing" else gg.n_u

    # ---- level-ascending node creation (collapsed chains)
    node_level = [0]
    parent = [-1]
    cur = np.zeros(n_ent, dtype=np.int32)       # deepest node so far
    entity_node = np.zeros(n_ent, dtype=np.int32)
    for li, k in enumerate(levels):
        lab = labels[li]
        alive = theta >= k
        own = theta == k
        own_roots = np.unique(lab[own])
        base = len(node_level)
        # parent BEFORE cur is updated: the deepest existing node that
        # contains the component's representative entity
        parent.extend(int(c) for c in cur[own_roots])
        node_level.extend([int(k)] * own_roots.size)
        remap = np.full(n_ent, -1, dtype=np.int64)
        remap[own_roots] = base + np.arange(own_roots.size)
        ali = np.where(alive)[0]
        mapped = remap[lab[ali]]
        hit = mapped >= 0
        cur[ali[hit]] = mapped[hit]
        entity_node[own] = cur[own]

    n_nodes = len(node_level)
    node_level = np.asarray(node_level, dtype=np.int64)
    parent = np.asarray(parent, dtype=np.int32)

    # ---- CSR packings
    member_cnt = np.bincount(entity_node, minlength=n_nodes)
    member_off = np.zeros(n_nodes + 1, dtype=np.int64)
    np.cumsum(member_cnt, out=member_off[1:])
    member_ids = np.argsort(entity_node, kind="stable").astype(np.int32)

    child_cnt = np.bincount(parent[1:], minlength=n_nodes)
    child_off = np.zeros(n_nodes + 1, dtype=np.int64)
    np.cumsum(child_cnt, out=child_off[1:])
    child_ids = (np.argsort(parent[1:], kind="stable") + 1).astype(np.int32)

    tin, tout = _dfs_order(n_nodes, child_off, child_ids)

    # ---- contiguous subtree slices: entities sorted by their node's tin
    ent_tin = tin[entity_node]
    ent_order = np.argsort(ent_tin, kind="stable").astype(np.int32)
    sorted_tin = ent_tin[ent_order]
    estart = np.searchsorted(sorted_tin, tin).astype(np.int64)
    eend = np.searchsorted(sorted_tin, tout).astype(np.int64)

    # ---- induced-subgraph stats per node, vectorized over the edges
    with obs.span("hierarchy.node_stats", cat="hierarchy") as sp:
        if sp is not None:
            sp.update(n_nodes=int(n_nodes))
        size = eend - estart
        subtree = (parent, tin, tout)
        if kind == "wing":
            node_m = size
            node_nu = _distinct_per_subtree(
                entity_node, gg.edges[:, 0], subtree)
            node_nv = _distinct_per_subtree(
                entity_node, gg.edges[:, 1], subtree)
        else:
            du, _ = gg.degrees()
            csum = np.zeros(n_ent + 1, dtype=np.int64)
            np.cumsum(du[ent_order], out=csum[1:])
            node_m = csum[eend] - csum[estart]
            node_nu = size
            node_nv = _distinct_per_subtree(
                entity_node[gg.edges[:, 0]], gg.edges[:, 1], subtree)

    span = node_nu * node_nv
    density = np.divide(
        node_m, span, out=np.zeros(n_nodes, dtype=np.float64),
        where=span > 0, casting="unsafe",
    )

    info = dict(kind=kind, side=side, n_entities=int(n_ent))
    info.update(prov)
    if meta:
        info.update(meta)

    return Hierarchy(
        kind=kind,
        n_entities=n_ent,
        theta=theta,
        node_level=node_level,
        parent=parent,
        entity_node=entity_node,
        member_off=member_off,
        member_ids=member_ids,
        child_off=child_off,
        child_ids=child_ids,
        tin=tin,
        tout=tout,
        ent_order=ent_order,
        estart=estart,
        eend=eend,
        node_m=node_m,
        node_nu=node_nu,
        node_nv=node_nv,
        density=density,
        meta=info,
    )
