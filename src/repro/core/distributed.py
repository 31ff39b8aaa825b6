"""Distributed PBNG — shard_map peeling for multi-device meshes.

Maps the paper's two phases onto an SPMD mesh:

* **CD** (coarse): the peeling structure (BE-Index *links* for the
  beindex engine, the flat *wedge list* / *pair list* for the csr tip
  and wing engines) is sharded across devices; each round every device
  computes its partial dying counts and per-entity losses with
  ``segment_sum`` and ``psum`` combines them.  One or two collectives
  per peeling round — the JAX statement of "little synchronization".
  Supports / frontier masks are replicated (O(n), tiny next to the
  index).  The round loop itself is ``core.peelspec.cd_loop`` — the
  same entity-agnostic driver the single-device engines run, with a
  :class:`~repro.core.peelspec.FixedTarget` range policy.

* **FD** (fine): partitions are padded to a common size, stacked on a
  leading axis and `shard_map`-ped over the ``peel`` mesh axis.  The
  per-partition cascade is ``core.peelspec._fd_while_device`` — **no
  collectives at all** — so the HLO proves the paper's "no global
  synchronization" claim structurally.

Used by ``launch/peel.py`` for the production-mesh dry-run and by the
multi-device tests (spawned with forced host device counts).
"""
from __future__ import annotations

import dataclasses
from functools import partial, wraps
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from . import csr
from .beindex import BEIndex, build_beindex
from .graph import BipartiteGraph
from .. import obs
from .peelspec import (
    FixedTarget,
    PeelResult,
    PeelSpec,
    PeelStats,
    _fd_while_device,
    cd_loop,
)

__all__ = [
    "ShardedWingState",
    "ShardedCSRState",
    "shard_links",
    "shard_wedges",
    "shard_wedges_pair_aligned",
    "shard_tip_pairs",
    "cd_round_sharded",
    "cd_round_sharded_csr",
    "make_cd_round_csr",
    "make_cd_round_csr_pair_aligned",
    "make_cd_round_tip_csr",
    "pack_fd_partitions",
    "pack_fd_partitions_csr",
    "pack_fd_partitions_tip_csr",
    "fd_peel_sharded",
    "fd_peel_sharded_csr",
    "fd_peel_sharded_tip_csr",
    "distributed_wing_decomposition",
    "distributed_tip_decomposition",
]


def _psum_staged(x, axis):
    """One logical psum, optionally staged over a hierarchical mesh.

    ``axis`` is a mesh-axis name (flat all-reduce, the default) or a
    tuple of names — e.g. ``("grp", "loc")`` on a 2-D mesh
    (:func:`repro.launch.mesh.make_peel_mesh_2d`).  A tuple lowers to
    staged all-reduces, innermost axis first: reduce WITHIN each group
    of co-located devices, then ACROSS groups — two small collectives
    with nested replica groups instead of one flat n-device ring, the
    classic hierarchical-reduction layout for rack-scale meshes.  All
    CD psums here ride int32, so every grouping is exact and the staged
    result is bit-identical to the flat one."""
    if isinstance(axis, str):
        return jax.lax.psum(x, axis)
    for a in reversed(axis):
        x = jax.lax.psum(x, a)
    return x


# =====================================================================
# CD — link-sharded rounds, one psum per round
# =====================================================================
@dataclasses.dataclass
class ShardedWingState:
    """Link-sharded CD state: index arrays split over the mesh axis,
    supports / bloom numbers replicated (O(m) + O(nb), tiny next to the
    links)."""

    le: jax.Array          # (L_pad,) link -> edge, sharded
    lt: jax.Array          # (L_pad,) link -> twin
    lb: jax.Array          # (L_pad,) link -> bloom
    alive_link: jax.Array  # (L_pad,) sharded
    k_alive: jax.Array     # (nb,) replicated
    support: jax.Array     # (m,) replicated
    nb: int
    m: int


def shard_links(be: BEIndex, m: int, n_dev: int) -> ShardedWingState:
    """Pad link arrays to a multiple of n_dev.  Pad links point at a
    sentinel dead bloom/edge and start dead."""
    L = be.n_links
    pad = (-L) % max(n_dev, 1)
    def padded(x, fill):
        return np.concatenate([x, np.full(pad, fill, dtype=x.dtype)])
    le = padded(be.link_edge, m)        # sentinel edge m
    lt = padded(be.link_twin, m)
    lb = padded(be.link_bloom, be.nb)   # sentinel bloom nb
    alive = np.concatenate([np.ones(L, bool), np.zeros(pad, bool)])
    return ShardedWingState(
        le=jnp.asarray(le), lt=jnp.asarray(lt), lb=jnp.asarray(lb),
        alive_link=jnp.asarray(alive),
        k_alive=jnp.asarray(be.bloom_k.astype(np.int32)),
        support=jnp.asarray(be.edge_support(m).astype(np.int32)),
        nb=be.nb, m=m,
    )


def _cd_round_body(peeled_pad, alive_link, k_alive, support_pad,
                   le, lt, lb, *, nb: int, m: int, axis: str | Tuple[str, ...]):
    """Runs per-shard under shard_map; one psum for c, one for loss."""
    pe = peeled_pad[le]
    pt = peeled_pad[lt]
    pair_dies = alive_link & (pe | pt)
    canon = le < lt
    c_local = jax.ops.segment_sum(
        (pair_dies & canon).astype(jnp.int32), lb, num_segments=nb + 1
    )
    c = _psum_staged(c_local, axis)
    widow = alive_link & ~pe & pt
    surv = alive_link & ~pair_dies
    contrib = jnp.where(widow, k_alive[lb] - 1, 0) + jnp.where(surv, c[lb], 0)
    loss_local = jax.ops.segment_sum(contrib, le, num_segments=m + 1)
    loss = _psum_staged(loss_local, axis)
    support_pad = support_pad - loss
    k_alive = k_alive - c[:nb]
    alive_link = alive_link & ~pair_dies
    return alive_link, k_alive, support_pad


def make_cd_round(mesh: Mesh, axis: str | Tuple[str, ...], nb: int, m: int):
    """Build the jitted, shard_map-ped CD round for a given mesh."""
    body = partial(_cd_round_body, nb=nb, m=m, axis=axis)
    spec_l = P(axis)
    spec_r = P()
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec_r, spec_l, spec_r, spec_r, spec_l, spec_l, spec_l),
        out_specs=(spec_l, spec_r, spec_r),
    )
    return jax.jit(fn)


def cd_round_sharded(round_fn, st: ShardedWingState, peeled: jax.Array
                     ) -> ShardedWingState:
    """One CD peeling round. ``peeled`` is the (m,) frontier mask."""
    peeled_pad = jnp.concatenate([peeled, jnp.zeros((1,), bool)])
    support_pad = jnp.concatenate([st.support, jnp.zeros((1,), jnp.int32)])
    alive_link, k_alive, support_pad = round_fn(
        peeled_pad, st.alive_link, st.k_alive, support_pad,
        st.le, st.lt, st.lb,
    )
    return dataclasses.replace(
        st, alive_link=alive_link, k_alive=k_alive, support=support_pad[:-1]
    )


# =====================================================================
# Aligned ("segment-on-one-shard") layouts — shared scaffolding
# =====================================================================
# Baseline CD pays TWO psums per round when its grouping segments
# (blooms for beindex, U-pairs for csr wing) straddle shards: one for
# the dying counts, one for the losses.  If every segment's items live
# on ONE shard the count state is shard-local and a round costs a
# single psum.  The greedy-balance placement and the scatter into
# [n_dev, Lmax] blocks are identical for every such layout (bloom-,
# pair- and vertex-aligned); only the per-item arrays differ.
def _greedy_balance(counts: np.ndarray, n_dev: int):
    """LPT-greedy segment→shard placement shared by the aligned one-psum
    CD layouts.

    Segments (blooms / U-pairs / vertices) are placed largest-first onto
    the least-loaded shard (heap, O(S log n_dev) — ties break to the
    lowest shard id like the original argmin).  Everything else is
    vectorized numpy: per shard, segments keep ascending-id order.
    Returns ``(shard_of, local_id, seg_start, loads, n_local)`` — per
    segment its shard, shard-local id and first item column; per shard
    its item load and segment count."""
    import heapq

    S = int(counts.size)
    if S == 0:
        z = np.zeros(0, dtype=np.int64)
        return z, z, z, np.zeros(n_dev, np.int64), np.zeros(n_dev, np.int64)
    shard_of = np.zeros(S, dtype=np.int64)
    heap = [(0, s) for s in range(max(n_dev, 1))]
    heapq.heapify(heap)
    for sid in np.argsort(-counts, kind="stable"):
        load, s = heapq.heappop(heap)
        shard_of[sid] = s
        heapq.heappush(heap, (load + int(counts[sid]), s))
    order = np.argsort(shard_of, kind="stable")   # group by shard, id-sorted
    grouped = shard_of[order]
    starts = np.flatnonzero(np.r_[True, np.diff(grouped) > 0])
    sizes = np.diff(np.r_[starts, S])
    rank = np.arange(S, dtype=np.int64) - np.repeat(starts, sizes)
    local_id = np.empty(S, dtype=np.int64)
    local_id[order] = rank
    cs = np.cumsum(counts[order]) - counts[order]  # items before, global
    seg_start = np.empty(S, dtype=np.int64)
    seg_start[order] = cs - np.repeat(cs[starts], sizes)
    loads = np.bincount(
        shard_of, weights=counts.astype(np.float64), minlength=n_dev
    ).astype(np.int64)
    n_local = np.bincount(shard_of, minlength=n_dev)
    return shard_of, local_id, seg_start, loads, n_local


def _aligned_layout(seg_ids: np.ndarray, n_seg: int, n_dev: int):
    """Entity-agnostic core of every aligned layout: greedy-balance
    segments over shards by item count, keeping ALL of a segment's items
    on one shard, and compute the block scatter.

    Returns ``(order, sh, pos, shard_of, loc_seg, Lmax, Smax,
    counts)``: sort the item arrays by ``order``, then
    ``arr_s[sh, pos] = arr[order]`` fills the [n_dev, Lmax] blocks;
    ``shard_of``/``loc_seg`` give each segment's shard and shard-local
    id (Smax = max local segments); ``counts`` the per-segment item
    counts (already computed for the balance — callers that need them
    must not re-derive)."""
    order = np.argsort(seg_ids, kind="stable")
    sorted_seg = seg_ids[order]
    counts = np.bincount(seg_ids, minlength=n_seg)
    shard_of, loc_seg, seg_start, loads, n_local = _greedy_balance(
        counts, n_dev)
    Lmax = max(int(loads.max()) if n_dev else 1, 1)
    Smax = max(int(n_local.max()) if n_local.size else 1, 1)
    if sorted_seg.size:
        off = np.zeros(n_seg + 1, dtype=np.int64)
        np.cumsum(counts, out=off[1:])
        sh = shard_of[sorted_seg]
        pos = (np.arange(sorted_seg.size, dtype=np.int64)
               - off[sorted_seg] + seg_start[sorted_seg])
    else:
        sh = pos = np.zeros(0, dtype=np.int64)
    return order, sh, pos, shard_of, loc_seg, Lmax, Smax, counts


def shard_links_bloom_aligned(be: BEIndex, m: int, n_dev: int) -> dict:
    """Greedy-balance blooms over shards by link count so every bloom's
    links land on ONE device; returns [n_dev, ...] blocks with
    shard-local bloom ids (see the one-psum rationale above)."""
    order, sh, pos, shard_of, loc_bloom, Lmax, Bmax, _ = _aligned_layout(
        be.link_bloom, be.nb, n_dev)
    le, lt, lb = (be.link_edge[order], be.link_twin[order],
                  be.link_bloom[order])

    le_s = np.full((n_dev, Lmax), m, np.int32)
    lt_s = np.full((n_dev, Lmax), m, np.int32)
    lb_s = np.full((n_dev, Lmax), Bmax, np.int32)
    alive = np.zeros((n_dev, Lmax), bool)
    k0 = np.zeros((n_dev, Bmax), np.int32)
    if lb.size:
        le_s[sh, pos] = le
        lt_s[sh, pos] = lt
        lb_s[sh, pos] = loc_bloom[lb]
        alive[sh, pos] = True
    if be.nb:
        k0[shard_of, loc_bloom] = be.bloom_k
    return dict(le=le_s, lt=lt_s, lb=lb_s, alive=alive, k0=k0,
                Bmax=Bmax, m=m)


def make_cd_round_bloom(mesh: Mesh, axis: str | Tuple[str, ...], Bmax: int, m: int):
    """One-psum CD round over bloom-aligned shards."""

    def body(peeled_pad, alive_link, k_alive, support_pad, le, lt, lb):
        # all per-shard [1, ...] blocks (leading shard axis split)
        pe = peeled_pad[le]
        pt = peeled_pad[lt]
        pair_dies = alive_link & (pe | pt)
        canon = le < lt
        c = jax.ops.segment_sum(
            (pair_dies & canon).astype(jnp.int32).reshape(-1),
            lb.reshape(-1), num_segments=Bmax + 1)  # LOCAL — no psum
        widow = alive_link & ~pe & pt
        surv = alive_link & ~pair_dies
        contrib = jnp.where(widow, k_alive.reshape(-1)[lb] - 1, 0) \
            + jnp.where(surv, c[lb], 0)
        loss = jax.ops.segment_sum(
            contrib.reshape(-1), le.reshape(-1), num_segments=m + 1)
        loss = _psum_staged(loss, axis)          # the ONLY collective
        support_pad = support_pad - loss
        k_alive = k_alive - c[:Bmax].reshape(k_alive.shape)
        alive_link = alive_link & ~pair_dies
        return alive_link, k_alive, support_pad

    spec_l = P(axis)
    spec_r = P()
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec_r, spec_l, spec_l, spec_r, spec_l, spec_l, spec_l),
        out_specs=(spec_l, spec_l, spec_r),
    )
    return jax.jit(fn)


# =====================================================================
# CD — wedge-sharded rounds for the csr engine (no BE-Index anywhere)
# =====================================================================
# Same two-psums-per-round structure as the link-sharded beindex CD, but
# the sharded unit is the flat wedge list (``core.csr.Wedges``): pairs
# play the role of blooms, per-pair alive wedge counts W_p the role of
# bloom numbers.  This is the only CD that scales with O(Σ deg²) memory
# — the engine that survives past the dense wall also shards.
@dataclasses.dataclass
class ShardedCSRState:
    """Wedge-sharded CD state: the flat wedge list split over the mesh
    axis, per-pair counts W and supports replicated."""

    we1: jax.Array         # (L_pad,) wedge -> edge 1, sharded (sentinel m)
    we2: jax.Array         # (L_pad,) wedge -> edge 2
    wp: jax.Array          # (L_pad,) wedge -> pair (sentinel n_pairs)
    alive_w: jax.Array     # (L_pad,) sharded
    W_pad: jax.Array       # (n_pairs+1,) replicated — alive wedges/pair
    support: jax.Array     # (m,) replicated
    n_pairs: int
    m: int


def shard_wedges(wed: csr.Wedges, n_dev: int) -> ShardedCSRState:
    """Pad the wedge list to a multiple of n_dev.  Pad wedges point at
    the sentinel edge m / pair n_pairs and start dead."""
    L = wed.n_wedges
    m = wed.m
    n_pairs = wed.n_pairs
    pad = (-L) % max(n_dev, 1)
    if L + pad == 0:
        pad = max(n_dev, 1)

    def padded(x, fill):
        return np.concatenate([x, np.full(pad, fill, dtype=x.dtype)])

    sup0 = csr.edge_butterflies0(wed)
    if sup0.size and int(sup0.max()) > 2 ** 31 - 1:
        raise OverflowError("wing supports exceed int32, the width of the "
                            "wing path's device counts")
    W_pad = np.zeros(n_pairs + 1, dtype=np.int32)
    W_pad[:n_pairs] = wed.W0.astype(np.int32)
    return ShardedCSRState(
        we1=jnp.asarray(padded(wed.wedge_e1, m)),
        we2=jnp.asarray(padded(wed.wedge_e2, m)),
        wp=jnp.asarray(padded(wed.wedge_pair, n_pairs)),
        alive_w=jnp.asarray(
            np.concatenate([np.ones(L, bool), np.zeros(pad, bool)])),
        W_pad=jnp.asarray(W_pad),
        support=jnp.asarray(sup0.astype(np.int32)),
        n_pairs=n_pairs, m=m,
    )


def _cd_round_body_csr(peeled_pad, alive_w, W_pad, support_pad,
                       we1, we2, wp, *, n_pairs: int, m: int, axis: str | Tuple[str, ...]):
    """Per-shard csr CD round (wing_loss_csr algebra + two psums)."""
    pe1 = peeled_pad[we1]
    pe2 = peeled_pad[we2]
    w_dies = alive_w & (pe1 | pe2)
    c_local = jax.ops.segment_sum(
        w_dies.astype(jnp.int32), wp, num_segments=n_pairs + 1
    )
    c = _psum_staged(c_local, axis)
    surv = alive_w & ~w_dies
    surv_loss = jnp.where(surv, c[wp], 0)
    loss_local = (
        jax.ops.segment_sum(
            jnp.where(w_dies & ~pe1, W_pad[wp] - 1, 0) + surv_loss,
            we1, num_segments=m + 1)
        + jax.ops.segment_sum(
            jnp.where(w_dies & ~pe2, W_pad[wp] - 1, 0) + surv_loss,
            we2, num_segments=m + 1)
    )
    loss = _psum_staged(loss_local, axis)
    return alive_w & ~w_dies, W_pad - c, support_pad - loss


def make_cd_round_csr(mesh: Mesh, axis: str | Tuple[str, ...], n_pairs: int, m: int):
    """Build the jitted, shard_map-ped csr CD round for a given mesh."""
    body = partial(_cd_round_body_csr, n_pairs=n_pairs, m=m, axis=axis)
    spec_l = P(axis)
    spec_r = P()
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec_r, spec_l, spec_r, spec_r, spec_l, spec_l, spec_l),
        out_specs=(spec_l, spec_r, spec_r),
    )
    return jax.jit(fn)


# =====================================================================
# CD variant — pair-aligned ("bloom-aligned") wedge sharding, one psum
# =====================================================================
# Baseline csr CD needs TWO psums per round: dying-wedge counts c_p
# (pairs straddle shards) then per-edge losses.  If every pair's wedges
# live on ONE shard — pairs play the role of blooms — c_p and W_p become
# shard-local state and a round costs a single psum (the loss): half the
# collectives, mirroring ``shard_links_bloom_aligned`` for the engine
# that scales past the BE-Index.
def shard_wedges_pair_aligned(wed: csr.Wedges, n_dev: int) -> dict:
    """Greedy-balance pairs over shards by wedge count (LPT-flavoured),
    keeping all of a pair's wedges on one shard with shard-local pair
    ids.  Returns [n_dev, ...] blocks: ``we1``/``we2`` (sentinel edge
    m), ``wp`` (local pair ids, sentinel Pmax), ``alive``, ``W0`` (local
    alive wedge counts, [n_dev, Pmax]), plus ``Pmax`` and ``m``."""
    m = wed.m
    n_pairs = wed.n_pairs
    order, sh, pos, shard_of, loc_pair, Lmax, Pmax, counts = (
        _aligned_layout(wed.wedge_pair, n_pairs, n_dev))
    we1, we2, wp = (wed.wedge_e1[order], wed.wedge_e2[order],
                    wed.wedge_pair[order])

    we1_s = np.full((n_dev, Lmax), m, np.int32)
    we2_s = np.full((n_dev, Lmax), m, np.int32)
    wp_s = np.full((n_dev, Lmax), Pmax, np.int32)
    alive = np.zeros((n_dev, Lmax), bool)
    W0 = np.zeros((n_dev, Pmax), np.int32)
    if wp.size:
        we1_s[sh, pos] = we1
        we2_s[sh, pos] = we2
        wp_s[sh, pos] = loc_pair[wp]
        alive[sh, pos] = True
    if n_pairs:
        W0[shard_of, loc_pair] = counts
    return dict(we1=we1_s, we2=we2_s, wp=wp_s, alive=alive, W0=W0,
                Pmax=Pmax, m=m)


def make_cd_round_csr_pair_aligned(mesh: Mesh, axis: str | Tuple[str, ...], Pmax: int, m: int):
    """One-psum csr CD round over pair-aligned wedge shards.

    Same widow/survivor algebra as :func:`_cd_round_body_csr`, but c_p
    and W_p are shard-local (a pair's wedges never straddle shards), so
    the per-edge loss reduction is the ONLY collective per round."""

    def body(peeled_pad, alive_w, W_loc, support_pad, we1, we2, wp):
        # all sharded inputs are per-shard [1, ...] blocks
        pe1 = peeled_pad[we1]
        pe2 = peeled_pad[we2]
        w_dies = alive_w & (pe1 | pe2)
        c = jax.ops.segment_sum(
            w_dies.astype(jnp.int32).reshape(-1),
            wp.reshape(-1), num_segments=Pmax + 1)   # LOCAL — no psum
        surv = alive_w & ~w_dies
        surv_loss = jnp.where(surv.reshape(-1), c[wp.reshape(-1)], 0)
        W_flat = W_loc.reshape(-1)
        Wm1 = jnp.concatenate([W_flat - 1, jnp.zeros((1,), jnp.int32)])
        loss_local = (
            jax.ops.segment_sum(
                jnp.where((w_dies & ~pe1).reshape(-1),
                          Wm1[wp.reshape(-1)], 0) + surv_loss,
                we1.reshape(-1), num_segments=m + 1)
            + jax.ops.segment_sum(
                jnp.where((w_dies & ~pe2).reshape(-1),
                          Wm1[wp.reshape(-1)], 0) + surv_loss,
                we2.reshape(-1), num_segments=m + 1)
        )
        loss = _psum_staged(loss_local, axis)        # the ONLY collective
        support_pad = support_pad - loss
        W_loc = W_loc - c[:Pmax].reshape(W_loc.shape)
        alive_w = alive_w & ~w_dies
        return alive_w, W_loc, support_pad

    spec_l = P(axis)
    spec_r = P()
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec_r, spec_l, spec_l, spec_r, spec_l, spec_l, spec_l),
        out_specs=(spec_l, spec_l, spec_r),
    )
    return jax.jit(fn)


def cd_round_sharded_csr(round_fn, st: ShardedCSRState, peeled: jax.Array
                         ) -> ShardedCSRState:
    """One csr CD peeling round. ``peeled`` is the (m,) frontier mask."""
    peeled_pad = jnp.concatenate([peeled, jnp.zeros((1,), bool)])
    support_pad = jnp.concatenate([st.support, jnp.zeros((1,), jnp.int32)])
    alive_w, W_pad, support_pad = round_fn(
        peeled_pad, st.alive_w, st.W_pad, support_pad,
        st.we1, st.we2, st.wp,
    )
    return dataclasses.replace(
        st, alive_w=alive_w, W_pad=W_pad, support=support_pad[:-1]
    )


# =====================================================================
# CD — tip csr: sharded pair incidence, ONE psum per round always
# =====================================================================
# Tip's CD update has NO cross-round sharded state: pair butterfly
# counts are static (V is never peeled), so a round is a single
# gather + segment_sum over directed pair entries (vertex u loses
# bf(u, u') when partner u' peels) and the per-vertex loss reduction is
# the ONLY collective regardless of layout.  ``aligned=True`` applies
# the generalized greedy balance so ALL of a vertex's entries land on
# one device — each vertex's loss is computed wholly locally (pure
# disjoint-support merge through the psum) and shards are balanced by
# incident-pair count instead of round-robin entry count.
def shard_tip_pairs(
    wed: csr.Wedges, pair_bf0: np.ndarray, n_dev: int,
    aligned: bool = False,
) -> dict:
    """Shard the directed pair-incidence list for the tip csr CD.

    Each pair {a, b} becomes two directed entries (dst=a, src=b) and
    (dst=b, src=a) carrying the static butterfly count, so a round's
    loss for dst is Σ bf over entries whose src peeled.  Returns
    [n_dev, Lmax] blocks ``dst``/``src`` (global vertex ids, sentinel
    n) and ``bf`` (0 on padding — algebra-neutral): round-robin split
    by default, vertex-aligned greedy balance with ``aligned=True``."""
    n = wed.n_u
    dst, src, val = csr.directed_pair_incidence(wed, pair_bf0)
    n_dev = max(n_dev, 1)
    if aligned:
        order, sh, pos, _, _, Lmax, _, _ = _aligned_layout(dst, n, n_dev)
        dst_s = np.full((n_dev, Lmax), n, np.int32)
        src_s = np.full((n_dev, Lmax), n, np.int32)
        bf_s = np.zeros((n_dev, Lmax), np.int32)
        if dst.size:
            dst_s[sh, pos] = dst[order]
            src_s[sh, pos] = src[order]
            bf_s[sh, pos] = val[order]
    else:
        L = dst.size
        Lmax = max(-(-L // n_dev), 1)
        pad = n_dev * Lmax - L
        dst_s = np.concatenate(
            [dst, np.full(pad, n, np.int64)]).astype(np.int32)
        src_s = np.concatenate(
            [src, np.full(pad, n, np.int64)]).astype(np.int32)
        bf_s = np.concatenate([val, np.zeros(pad, np.int32)])
        dst_s = dst_s.reshape(n_dev, Lmax)
        src_s = src_s.reshape(n_dev, Lmax)
        bf_s = bf_s.reshape(n_dev, Lmax)
    return dict(dst=dst_s, src=src_s, bf=bf_s, n=n)


def make_cd_round_tip_csr(mesh: Mesh, axis: str | Tuple[str, ...], n: int):
    """One-psum tip csr CD round over sharded pair-incidence blocks.

    The same jitted round serves both layouts of :func:`shard_tip_pairs`
    (round-robin and vertex-aligned): pair butterflies are static, so
    the per-vertex loss reduction is the single collective either way.
    """

    def body(peeled_pad, support_pad, dst, src, bf):
        contrib = jnp.where(peeled_pad[src.reshape(-1)], bf.reshape(-1), 0)
        loss = jax.ops.segment_sum(
            contrib, dst.reshape(-1), num_segments=n + 1)
        loss = _psum_staged(loss, axis)          # the ONLY collective
        return support_pad - loss

    spec_l = P(axis)
    spec_r = P()
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(spec_r, spec_r, spec_l, spec_l, spec_l),
        out_specs=spec_r,
    )
    return jax.jit(fn)


# =====================================================================
# FD — partition-stacked, communication-free shard_map
# =====================================================================
def pack_fd_partitions(
    g: BipartiteGraph, be: BEIndex, part: np.ndarray, sup_init: np.ndarray,
    n_parts: int, pad_to: Optional[int] = None,
) -> dict:
    """Build [n_parts_padded, ...] stacked local sub-indices (alg.5).

    Local ids per partition; twins outside the partition map to a
    sentinel never-peeled slot.  Everything padded so partitions stack.
    """
    ple = part[be.link_edge]
    plt_ = part[be.link_twin]
    canon_full = be.link_edge < be.link_twin
    per = []
    for i in range(n_parts):
        mine_idx = np.where(part == i)[0]
        loc = np.full(g.m, -1, dtype=np.int64)
        loc[mine_idx] = np.arange(mine_idx.size)
        pair_ge = (ple >= i) & (plt_ >= i)
        # only links anchored at a local (peelable) edge; cross-partition
        # pairs therefore appear exactly once
        keep = pair_ge & (ple == i)
        k_init = np.zeros(be.nb, dtype=np.int64)
        np.add.at(k_init, be.link_bloom[pair_ge & canon_full], 1)
        kl_e, kl_t, kl_b = (be.link_edge[keep], be.link_twin[keep],
                            be.link_bloom[keep])
        twin_local = part[kl_t] == i
        # count each dying pair once: both-local pairs via id order,
        # cross pairs via their single link
        canon = np.where(twin_local, kl_e < kl_t, True)
        blooms = np.unique(kl_b)
        bloc = np.full(be.nb + 1, 0, dtype=np.int64)
        if blooms.size:
            bloc[blooms] = np.arange(blooms.size)
        per.append(dict(
            edges=mine_idx,
            le=loc[kl_e], lt=np.where(twin_local, loc[kl_t], -1),
            lb=bloc[kl_b], canon=canon,
            k0=k_init[blooms],
            sup0=sup_init[mine_idx],
        ))
    Lmax = max((p["le"].size for p in per), default=1) or 1
    Emax = max((p["edges"].size for p in per), default=1) or 1
    Bmax = max((p["k0"].size for p in per), default=1) or 1
    if pad_to:
        Lmax, Emax, Bmax = (max(Lmax, pad_to), max(Emax, pad_to),
                            max(Bmax, pad_to))

    def pk(key, size, fill, dtype=np.int32):
        out = np.full((n_parts, size), fill, dtype=dtype)
        for i, p in enumerate(per):
            x = p[key]
            out[i, : x.size] = x
        return out

    # sentinel local edge id = Emax (extra never-peeled slot)
    le = pk("le", Lmax, Emax)
    lt = np.where(pk("lt", Lmax, -1) < 0, Emax,
                  pk("lt", Lmax, -1)).astype(np.int32)
    canon = pk("canon", Lmax, 0, dtype=bool)
    alive0 = np.zeros((n_parts, Lmax), dtype=bool)
    for i, p in enumerate(per):
        alive0[i, : p["le"].size] = True
    mine = np.zeros((n_parts, Emax), dtype=bool)
    sup0 = np.zeros((n_parts, Emax), dtype=np.int32)
    gids = np.zeros((n_parts, Emax), dtype=np.int32)
    for i, p in enumerate(per):
        mine[i, : p["edges"].size] = True
        sup0[i, : p["edges"].size] = p["sup0"]
        gids[i, : p["edges"].size] = p["edges"]
    k0 = pk("k0", Bmax, 0)
    return dict(
        le=le, lt=lt, lb=pk("lb", Lmax, Bmax - 1), alive0=alive0,
        canon=canon, k0=k0, sup0=sup0, mine=mine, gids=gids,
        sizes=(Lmax, Emax, Bmax),
    )


def _fd_body_one_partition(le, lt, lb, alive0, canon, k0, sup0, mine):
    """Peel one beindex partition bottom-up — the shared device FD
    driver (``peelspec._fd_while_device``) with the alg.6 widow/survivor
    update: one while_loop, NO collectives."""
    Emax = mine.shape[0]
    Bmax = k0.shape[0]

    def update(S, aux):
        alive_link, k_alive = aux
        pe = jnp.concatenate([S, jnp.zeros((1,), bool)])
        p_e = pe[le]
        p_t = pe[lt]
        pair_dies = alive_link & (p_e | p_t)
        c = jax.ops.segment_sum(
            (pair_dies & canon).astype(jnp.int32), lb, num_segments=Bmax)
        widow = alive_link & ~p_e & p_t
        surv = alive_link & ~pair_dies
        contrib = jnp.where(widow, k_alive[lb] - 1, 0) + jnp.where(
            surv, c[lb], 0)
        loss = jax.ops.segment_sum(contrib, le, num_segments=Emax + 1)[:-1]
        return loss, (alive_link & ~pair_dies, k_alive - c), jnp.int32(0)

    theta, rounds, _ = _fd_while_device(
        mine, sup0.astype(jnp.int32), update,
        (alive0, k0.astype(jnp.int32)),
    )
    return theta, rounds


def _fd_run_sharded(body, packed: dict, keys: Tuple[str, ...],
                    mesh: Mesh, axis: str | Tuple[str, ...]) -> Tuple[np.ndarray, np.ndarray]:
    """Shared FD launcher: pad the partition axis to the device count,
    shard_map the vmapped per-partition body, trim the results."""
    n_parts = packed[keys[0]].shape[0]
    n_dev = mesh.devices.size
    pad = (-n_parts) % n_dev

    def padp(x):
        if pad == 0:
            return jnp.asarray(x)
        fill = np.zeros((pad,) + x.shape[1:], dtype=x.dtype)
        return jnp.asarray(np.concatenate([x, fill], axis=0))

    args = tuple(padp(packed[k]) for k in keys)
    fn = shard_map(
        jax.vmap(body), mesh=mesh,
        in_specs=tuple(P(axis) for _ in args),
        out_specs=(P(axis), P(axis)),
    )
    theta, rounds = jax.jit(fn)(*args)
    return np.asarray(theta)[:n_parts], np.asarray(rounds)[:n_parts]


def fd_peel_sharded(packed: dict, mesh: Mesh, axis: str | Tuple[str, ...]
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Peel all partitions concurrently: shard_map over the partition axis
    (device-parallel), vmap within a shard.  Returns (theta[m'], rounds[P])
    in packed local layout."""
    return _fd_run_sharded(
        _fd_body_one_partition, packed,
        ("le", "lt", "lb", "alive0", "canon", "k0", "sup0", "mine"),
        mesh, axis,
    )


# =====================================================================
# FD — csr variant: partition-stacked wedge lists, zero collectives
# =====================================================================
def pack_fd_partitions_csr(
    wed: csr.Wedges, part: np.ndarray, sup_init: np.ndarray,
    n_parts: int, pad_to: Optional[int] = None,
    bucket: bool = False, slots: bool = False, flat: bool = False,
) -> dict:
    """Stack per-partition wedge sub-lists into [n_parts, ...] arrays.

    Partition i's sub-structure = wedges with both edges in partitions
    ≥ i (the same induced subgraph the single-device csr FD uses); edge
    ids are partition-local with a sentinel slot Emax for never-peeled
    later-partition edges, pair ids are relabeled per partition.  Same
    sentinel/pad machinery as :func:`pack_fd_partitions`.

    ``bucket=True`` rounds the stacked dims (Lmax, Emax, Pmax) up to
    quarter-power-of-two buckets (``peelspec._bucket_pad``) so the
    jitted single-dispatch FD driver (``peelspec._fd_while_vmapped``
    consumers) recompiles once per shape *bucket* instead of once per
    partition layout — the same trick the per-partition launcher used,
    applied to the whole stack.  Partitions whose individual sizes
    straddle different buckets still land in ONE stacked layout (and
    therefore one while_loop); the bucket only bounds recompiles across
    graphs.

    ``flat=True`` additionally emits the ragged-concatenated arrays the
    single-device single-dispatch driver consumes (see
    :func:`_pack_fd_flat_csr` — the touching-wedge lists are disjoint,
    so concatenation carries zero padding waste).

    ``slots=True`` additionally packs each partition's wedge list into
    the pairs-major slot layout the blocked Pallas ``support_update``
    kernel consumes (`core.csr.PaddedCSR` per partition, stacked):
    ``slot_e1``/``slot_e2`` are [n_parts, R, K] partition-local edge ids
    (sentinel Emax on padding slots), ``slot_valid`` the initial alive
    matrix.  Rows of all partitions share one (R, K) shape so the FD
    while_loop body can flatten the partition axis into the kernel's row
    grid — one kernel launch per round covering every partition."""
    m = part.size
    pe1 = part[wed.wedge_e1] if wed.n_wedges else np.zeros(0, np.int32)
    pe2 = part[wed.wedge_e2] if wed.n_wedges else np.zeros(0, np.int32)
    pmin = np.minimum(pe1, pe2)
    per = []
    for i in range(n_parts):
        mine_idx = np.where(part == i)[0]
        loc = np.full(m, -1, dtype=np.int64)
        loc[mine_idx] = np.arange(mine_idx.size)
        keep_ge = (pe1 >= i) & (pe2 >= i)
        # only wedges TOUCHING partition i can die during FD_i (edges of
        # later partitions never peel here), and survivor charges from
        # untouched ≥i wedges land only on discarded later-partition
        # edges — so the wedge list holds the touching wedges while the
        # untouched ones fold into the static W0 count (they stay alive
        # the whole phase).  Exact, and it makes the stacked lists
        # disjoint across partitions: each wedge appears exactly once,
        # in partition min(part[e1], part[e2]).
        keep = keep_ge & (pmin == i)
        kwe1 = wed.wedge_e1[keep]
        kwe2 = wed.wedge_e2[keep]
        pair_ids, wp_loc = np.unique(wed.wedge_pair[keep],
                                     return_inverse=True)
        cnt_ge = np.bincount(wed.wedge_pair[keep_ge],
                             minlength=max(wed.n_pairs, 1))
        per.append(dict(
            edges=mine_idx,
            we1=np.where(part[kwe1] == i, loc[kwe1], -1),
            we2=np.where(part[kwe2] == i, loc[kwe2], -1),
            wp=wp_loc,
            W0=(cnt_ge[pair_ids] if pair_ids.size
                else np.zeros(1, np.int64)),
            sup0=sup_init[mine_idx],
        ))
    Lmax = max((p["we1"].size for p in per), default=1) or 1
    Emax = max((p["edges"].size for p in per), default=1) or 1
    Pmax = max((p["W0"].size for p in per), default=1) or 1
    if bucket:
        from .peelspec import _bucket_pad

        Lmax = _bucket_pad(Lmax)
        Emax = _bucket_pad(Emax, floor=8)
        Pmax = _bucket_pad(Pmax, floor=8)
    if pad_to:
        Lmax, Emax, Pmax = (max(Lmax, pad_to), max(Emax, pad_to),
                            max(Pmax, pad_to))

    def pk(key, size, fill, dtype=np.int32):
        out = np.full((n_parts, size), fill, dtype=dtype)
        for i, p in enumerate(per):
            x = p[key]
            out[i, : x.size] = x
        return out

    # sentinel local edge id = Emax (extra never-peeled slot); pad wedges
    # carry pair 0 but start dead, so they contribute nothing
    w1 = pk("we1", Lmax, -1)
    w2 = pk("we2", Lmax, -1)
    we1 = np.where(w1 < 0, Emax, w1).astype(np.int32)
    we2 = np.where(w2 < 0, Emax, w2).astype(np.int32)
    alive0 = np.zeros((n_parts, Lmax), dtype=bool)
    mine = np.zeros((n_parts, Emax), dtype=bool)
    sup0 = np.zeros((n_parts, Emax), dtype=np.int32)
    gids = np.zeros((n_parts, Emax), dtype=np.int32)
    for i, p in enumerate(per):
        alive0[i, : p["we1"].size] = True
        mine[i, : p["edges"].size] = True
        sup0[i, : p["edges"].size] = p["sup0"]
        gids[i, : p["edges"].size] = p["edges"]
    packed = dict(
        we1=we1, we2=we2, wp=pk("wp", Lmax, 0), alive0=alive0,
        W0=pk("W0", Pmax, 0), sup0=sup0, mine=mine, gids=gids,
        sizes=(Lmax, Emax, Pmax),
    )
    if flat:
        packed.update(_pack_fd_flat_csr(per, n_parts, Emax, bucket=bucket))
    if slots:
        packed.update(_pack_fd_slots_csr(per, n_parts, Emax, bucket=bucket))
    return packed


def _pack_fd_flat_csr(per: list, n_parts: int, Emax: int,
                      bucket: bool = False) -> dict:
    """Ragged-concatenated wedge arrays for the single-dispatch FD.

    The touching-wedge lists are disjoint across partitions, so instead
    of stacking them [n_parts, Lmax] (up to Lmax/mean padding waste) the
    single-device vmapped driver concatenates them into ONE flat list
    with pre-globalized segment ids: partition b's local edge e becomes
    segment b·(Emax+1)+e, its local pair p becomes base_b+p.  Per-round
    work is then O(Σ|list_i|) regardless of partition imbalance.  Pad
    wedges (bucketed tail) point at partition 0's sentinel edge and a
    dedicated dead pair and start dead."""
    sizes = [p["wp"].size for p in per]
    npairs = [int(p["W0"].size) for p in per]
    pair_base = np.zeros(n_parts + 1, dtype=np.int64)
    np.cumsum(npairs, out=pair_base[1:])
    Ptot = int(pair_base[-1])
    Wtot = int(sum(sizes))
    Wpad = Wtot
    Ppad = Ptot + 1
    if bucket:
        from .peelspec import _bucket_pad

        Wpad = _bucket_pad(max(Wtot, 1))
        Ppad = _bucket_pad(Ptot + 1, floor=8)
    fe1 = np.full(Wpad, Emax, dtype=np.int32)   # partition-0 sentinel
    fe2 = np.full(Wpad, Emax, dtype=np.int32)
    fwp = np.full(Wpad, Ptot, dtype=np.int32)   # dedicated dead pair
    falive = np.zeros(Wpad, dtype=bool)
    fW0 = np.zeros(Ppad, dtype=np.int32)
    pos = 0
    for i, p in enumerate(per):
        k = p["wp"].size
        off = i * (Emax + 1)
        e1 = np.where(p["we1"] < 0, Emax, p["we1"]) + off
        e2 = np.where(p["we2"] < 0, Emax, p["we2"]) + off
        fe1[pos: pos + k] = e1
        fe2[pos: pos + k] = e2
        fwp[pos: pos + k] = p["wp"] + pair_base[i]
        falive[pos: pos + k] = True
        fW0[pair_base[i]: pair_base[i + 1]] = p["W0"]
        pos += k
    return dict(flat_we1=fe1, flat_we2=fe2, flat_wp=fwp,
                flat_alive0=falive, flat_W0=fW0,
                flat_sizes=(Wpad, Ppad))


def _pack_fd_slots_csr(per: list, n_parts: int, Emax: int,
                       bucket: bool = False) -> dict:
    """Stacked pairs-major slot layout for the Pallas in-loop FD update.

    Row r of partition i's block holds the wedges of local pair r
    (``core.csr.pad_segments`` per partition), all blocks padded to one
    (R, K) shape.  Slot edge ids are partition-local with sentinel Emax
    (the extra never-peeled edge slot), so the FD body's peeled-flag
    gathers and loss scatters need no masking."""
    # the kernel carries counts as f32 — same exactness boundary as
    # core.csr.pack_update_slots (W only decreases; checking W0 suffices)
    wmax = max((int(p["W0"].max()) if p["W0"].size else 0 for p in per),
               default=0)
    if wmax >= 2 ** 24:
        raise OverflowError(
            "pair wedge counts exceed f32 integer range (2^24); "
            "use the segment_sum FD body (use_pallas=False)")
    packs = [csr.pad_segments(p["wp"].astype(np.int64),
                              max(p["W0"].size, 1)) for p in per]
    R = max((pk.n_rows_pad for pk in packs), default=1) or 1
    K = max((pk.width for pk in packs), default=1) or 1
    if bucket:
        from .peelspec import _bucket_pad

        R = _bucket_pad(R, floor=8)
        K = _bucket_pad(K, floor=128)
    slot_e1 = np.full((n_parts, R, K), Emax, dtype=np.int32)
    slot_e2 = np.full((n_parts, R, K), Emax, dtype=np.int32)
    slot_valid = np.zeros((n_parts, R, K), dtype=bool)
    for i, (p, pk) in enumerate(zip(per, packs)):
        if p["wp"].size == 0:
            continue
        idx = np.maximum(pk.idx, 0)
        # local edge ids; -1 (edge of a later partition) → sentinel Emax
        e1 = np.where(p["we1"] < 0, Emax, p["we1"]).astype(np.int32)
        e2 = np.where(p["we2"] < 0, Emax, p["we2"]).astype(np.int32)
        r, c = pk.idx.shape
        slot_e1[i, :r, :c] = np.where(pk.valid, e1[idx], Emax)
        slot_e2[i, :r, :c] = np.where(pk.valid, e2[idx], Emax)
        slot_valid[i, :r, :c] = pk.valid
    return dict(slot_e1=slot_e1, slot_e2=slot_e2, slot_valid=slot_valid,
                slot_sizes=(R, K))


def pack_fd_partitions_tip_csr(
    wed: csr.TipPairs, pair_bf0: np.ndarray, part: np.ndarray,
    sup_init: np.ndarray, n_parts: int, bucket: bool = False,
    stacked: bool = False, dtype=np.int32,
) -> dict:
    """Tip counterpart of :func:`pack_fd_partitions_csr`.

    Tip FD needs only the pairs with BOTH endpoints inside the partition
    (vertices of later partitions never peel during FD_i and deltas onto
    them are discarded), so the stacked pair lists are disjoint across
    partitions — no duplication.  Pair butterfly counts are static (the
    V side is never peeled), so there is no per-partition wedge state:
    pad pairs carry bf=0 and are algebra-neutral.

    The kept pair lists are disjoint across partitions (each pair lives
    where both endpoints do), so they concatenate ragged with
    pre-globalized vertex ids — zero stacking padding.  Returns
    ``pa``/``pb`` (W,) globalized segment ids b·Emax+u, ``bf`` (W,)
    static pair butterflies (0 on the bucketed pad tail — algebra
    neutral), plus [n_parts, Emax] ``mine``/``sup0``/``gids``.

    ``stacked=True`` additionally emits the [n_parts, Lmax] blocks
    ``st_pa``/``st_pb``/``st_bf`` (partition-LOCAL vertex ids, bf=0 on
    padding) the per-partition shard_map FD
    (:func:`fd_peel_sharded_tip_csr`) consumes.  ``dtype`` is the count
    width of ``bf`` and ``sup0`` (int64 where ``csr.count_bits`` asks
    for it); the stacked blocks feed int32 kernels and stay int32."""
    n = part.size
    pa_p = part[wed.pair_a] if wed.n_pairs else np.zeros(0, np.int32)
    pb_p = part[wed.pair_b] if wed.n_pairs else np.zeros(0, np.int32)
    per = []
    for i in range(n_parts):
        mine_idx = np.where(part == i)[0]
        loc = np.full(n, -1, dtype=np.int64)
        loc[mine_idx] = np.arange(mine_idx.size)
        keep = (pa_p == i) & (pb_p == i)
        per.append(dict(
            nodes=mine_idx,
            pa=loc[wed.pair_a[keep]], pb=loc[wed.pair_b[keep]],
            bf=pair_bf0[keep].astype(dtype),
            sup0=sup_init[mine_idx],
        ))
    Emax = max((p["nodes"].size for p in per), default=1) or 1
    Wtot = int(sum(p["pa"].size for p in per))
    Wpad = max(Wtot, 1)
    if bucket:
        from .peelspec import _bucket_pad

        Emax = _bucket_pad(Emax, floor=8)
        Wpad = _bucket_pad(Wpad)
    pa = np.zeros(Wpad, dtype=np.int32)
    pb = np.zeros(Wpad, dtype=np.int32)
    bf = np.zeros(Wpad, dtype=dtype)
    mine = np.zeros((n_parts, Emax), dtype=bool)
    sup0 = np.zeros((n_parts, Emax), dtype=dtype)
    gids = np.zeros((n_parts, Emax), dtype=np.int32)
    pos = 0
    for i, p in enumerate(per):
        k = p["pa"].size
        pa[pos: pos + k] = p["pa"] + i * Emax
        pb[pos: pos + k] = p["pb"] + i * Emax
        bf[pos: pos + k] = p["bf"]
        pos += k
        mine[i, : p["nodes"].size] = True
        sup0[i, : p["nodes"].size] = p["sup0"]
        gids[i, : p["nodes"].size] = p["nodes"]
    packed = dict(pa=pa, pb=pb, bf=bf, mine=mine, sup0=sup0, gids=gids,
                  sizes=(Wpad, Emax))
    if stacked:
        Lmax = max((p["pa"].size for p in per), default=1) or 1
        if bucket:
            from .peelspec import _bucket_pad

            Lmax = _bucket_pad(Lmax, floor=8)
        st_pa = np.zeros((n_parts, Lmax), dtype=np.int32)
        st_pb = np.zeros((n_parts, Lmax), dtype=np.int32)
        st_bf = np.zeros((n_parts, Lmax), dtype=np.int32)
        for i, p in enumerate(per):
            k = p["pa"].size
            st_pa[i, :k] = p["pa"]
            st_pb[i, :k] = p["pb"]
            st_bf[i, :k] = p["bf"]
        packed.update(st_pa=st_pa, st_pb=st_pb, st_bf=st_bf)
    return packed


def _fd_body_one_partition_csr(we1, we2, wp, alive0, W0, sup0, mine):
    """Peel one csr wing partition bottom-up — the shared device FD
    driver (``peelspec._fd_while_device``): one while_loop, NO
    collectives."""
    Emax = mine.shape[0]
    Pmax = W0.shape[0]

    def update(S, aux):
        alive_w, W = aux
        S_pad = jnp.concatenate([S, jnp.zeros((1,), bool)])
        alive_w, W, loss, _ = csr.wing_loss_csr(
            S_pad, alive_w, W, we1, we2, wp, Pmax, Emax + 1
        )
        return loss[:Emax], (alive_w, W), jnp.int32(0)

    theta, rounds, _ = _fd_while_device(
        mine, sup0.astype(jnp.int32), update,
        (alive0, W0.astype(jnp.int32)),
    )
    return theta, rounds


def _fd_body_one_partition_tip_csr(pa, pb, bf, mine, sup0):
    """Peel one csr tip partition bottom-up — the shared device FD
    driver with the static pair-butterfly update: one while_loop, NO
    collectives."""
    Emax = mine.shape[0]

    def update(S, aux):
        loss = csr.tip_delta_csr(S, pa, pb, bf, Emax)
        return loss, aux, jnp.int32(0)

    theta, rounds, _ = _fd_while_device(
        mine, sup0.astype(jnp.int32), update, jnp.int32(0))
    return theta, rounds


def fd_peel_sharded_csr(packed: dict, mesh: Mesh, axis: str | Tuple[str, ...]
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """csr wing counterpart of :func:`fd_peel_sharded` — shard_map over
    the padded wedge-slot stacks, zero collectives inside partitions."""
    return _fd_run_sharded(
        _fd_body_one_partition_csr, packed,
        ("we1", "we2", "wp", "alive0", "W0", "sup0", "mine"),
        mesh, axis,
    )


def fd_peel_sharded_tip_csr(packed: dict, mesh: Mesh, axis: str | Tuple[str, ...]
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """csr tip counterpart of :func:`fd_peel_sharded` — shard_map over
    the stacked local pair lists (``pack_fd_partitions_tip_csr`` with
    ``stacked=True``), zero collectives inside partitions."""
    return _fd_run_sharded(
        _fd_body_one_partition_tip_csr, packed,
        ("st_pa", "st_pb", "st_bf", "mine", "sup0"),
        mesh, axis,
    )


# =====================================================================
# End-to-end distributed wing decomposition
# =====================================================================
def _place_sharded(mesh: Mesh, axis: str | Tuple[str, ...], **arrays):
    """Put each CD input on the mesh once, split along its leading axis
    the way the round's ``P(axis)`` in_spec reads it.  A host or
    single-device array would instead be re-sent to every shard on each
    round.  Returns the placed arrays and, per name, the device ids and
    shape of its addressable shards (``cd_shards`` in the stats)."""
    sharding = NamedSharding(mesh, P(axis))
    placed = {k: jax.device_put(v, sharding) for k, v in arrays.items()}
    where = {
        k: dict(devices=[int(s.device.id) for s in v.addressable_shards],
                shard_shape=list(v.addressable_shards[0].data.shape))
        for k, v in placed.items()}
    return placed, where


def _scatter_theta(theta, packed, theta_loc, n_parts):
    """Map packed-local θ back to global entity ids."""
    for i in range(n_parts):
        mine = packed["mine"][i]
        theta[packed["gids"][i][mine]] = theta_loc[i][mine]


def _finish(theta, part, ranges, sup_init, stats, extras, return_result):
    """Assemble the (theta, stats[, PeelResult]) return of the
    distributed decompositions: JSON-able stats dict with the mesh
    extras, full provenance only when asked for."""
    stats_out = stats.as_dict()
    stats_out.update(extras)
    if not return_result:
        return theta, stats_out
    result = PeelResult(
        theta=theta, part=part, ranges=ranges,
        support_init=sup_init, stats=stats,
    )
    return theta, stats_out, result


def _record_fd_sharded(n_parts: int, rounds) -> None:
    """Record a sharded FD launch's per-partition round counts into the
    active timeline collector (per-round rings don't cross the
    ``shard_map`` boundary; totals stay exact)."""
    col = obs.active_collector()
    if col is not None and n_parts:
        r = np.asarray(rounds).reshape(-1)[:n_parts]
        col.record_fd_counts(
            "sharded", list(range(n_parts)),
            r.astype(np.int64).tolist())


def _with_obs(kind: str):
    """Wrap a distributed decomposition entry with the observability
    collector: a ``peel``-cat span around the run, a timeline built from
    the collector (CD rounds recorded live by ``cd_loop``; FD round
    counts recorded by the sharded/vmapped FD sections), its trace
    events, and attachment to the returned stats dict / PeelResult.
    With the obs layer off this adds one ``is None`` check."""
    def deco(fn):
        @wraps(fn)
        def wrapper(*args, **kwargs):
            with obs.maybe_collect() as col:
                with obs.span(f"peel.{fn.__name__}", cat="peel",
                              kind=kind):
                    out = fn(*args, **kwargs)
            if col is not None:
                tl = col.build()
                tracer = obs.get_tracer()
                if tracer is not None:
                    tl.emit_trace_events(tracer)
                out[1]["timeline"] = tl.summary()
                if len(out) == 3:
                    out[2].timeline = tl
            return out
        return wrapper
    return deco


@_with_obs("wing")
def distributed_wing_decomposition(
    g: BipartiteGraph,
    mesh: Mesh,
    axis: str | Tuple[str, ...] = "peel",
    P_parts: int = 8,
    be: Optional[BEIndex] = None,
    bloom_aligned: bool = False,
    engine: str = "beindex",
    pair_aligned: bool = False,
    aligned: Optional[bool] = None,
    return_result: bool = False,
):
    """Full PBNG wing decomposition on a device mesh.

    ``engine="beindex"``: link-sharded CD rounds (two psums;
    ``bloom_aligned=True`` uses the one-psum §Perf variant) + link-packed
    FD.  ``engine="csr"``: wedge-sharded CD rounds + wedge-packed FD —
    O(Σ deg²) memory end to end, no BE-Index built;
    ``pair_aligned=True`` shards wedges pair-aligned (all of a pair's
    wedges on one device) so the dying-count reduction c_p is
    shard-local and CD pays ONE psum per round instead of two.  FD is
    communication-free either way.

    ``aligned`` is the entity-agnostic spelling of the one-psum layout
    (the flag ``launch/peel.py`` passes for both tip and wing): it maps
    to ``pair_aligned`` for csr and ``bloom_aligned`` for beindex.

    Returns ``(theta, stats)`` — ``return_result=True`` appends the full
    :class:`~repro.core.peelspec.PeelResult` (partition provenance for
    the hierarchy serializer).

    Example (8 forced host devices)::

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        theta, stats = distributed_wing_decomposition(
            g, mesh, engine="csr", pair_aligned=True)
    """
    if engine not in ("beindex", "csr"):
        raise ValueError(engine)
    if aligned is not None:
        if engine == "csr":
            pair_aligned = aligned
        else:
            bloom_aligned = aligned
    if pair_aligned and engine != "csr":
        raise ValueError(
            "pair_aligned shards the wedge list: csr engine only "
            "(the beindex analogue is bloom_aligned)"
        )
    if engine == "csr":
        if bloom_aligned or be is not None:
            raise ValueError(
                "engine='csr' builds no BE-Index: bloom_aligned/be "
                "only apply to engine='beindex'"
            )
        return _distributed_wing_csr(
            g, mesh, axis, P_parts, pair_aligned=pair_aligned,
            return_result=return_result)
    if be is None:
        be = build_beindex(g)
    m = g.m
    n_dev = mesh.devices.size
    if bloom_aligned:
        packed = shard_links_bloom_aligned(be, m, n_dev)
        round_fn = make_cd_round_bloom(mesh, axis, packed["Bmax"], m)
        bl_alive = jnp.asarray(packed["alive"])
        bl_k = jnp.asarray(packed["k0"])
        bl_le = jnp.asarray(packed["le"])
        bl_lt = jnp.asarray(packed["lt"])
        bl_lb = jnp.asarray(packed["lb"])
        support = jnp.asarray(be.edge_support(m).astype(np.int32))
        st = None
    else:
        st = shard_links(be, m, n_dev)
        round_fn = make_cd_round(mesh, axis, st.nb, m)
        support = st.support

    def step(active: np.ndarray) -> np.ndarray:
        nonlocal st, support, bl_alive, bl_k
        if bloom_aligned:
            peeled_pad = jnp.concatenate(
                [jnp.asarray(active), jnp.zeros((1,), bool)])
            support_pad = jnp.concatenate(
                [support, jnp.zeros((1,), jnp.int32)])
            bl_alive, bl_k, support_pad = round_fn(
                peeled_pad, bl_alive, bl_k, support_pad,
                bl_le, bl_lt, bl_lb)
            support = support_pad[:-1]
            return np.asarray(support).astype(np.int64)
        st = cd_round_sharded(round_fn, st, jnp.asarray(active))
        return np.asarray(st.support).astype(np.int64)

    stats = PeelStats(engine="beindex", fd_driver="device")
    sup0 = np.asarray(support).astype(np.int64)
    spec = PeelSpec(
        kind="wing", n=m, sup0=sup0,
        workload=lambda s: np.maximum(s, 1), est=lambda s: s,
        cd_step=step,
    )
    with obs.span("cd", cat="cd"):
        part, sup_init, ranges, n_parts = cd_loop(
            spec, P_parts, stats,
            target=FixedTarget(float(sup0.sum()), P_parts))

    with obs.span("fd", cat="fd", driver="sharded") as sp:
        packed = pack_fd_partitions(g, be, part, sup_init, n_parts)
        theta_loc, rounds = fd_peel_sharded(packed, mesh, axis)
        if sp is not None:
            sp.update(rounds=int(rounds.sum()))
    theta = np.zeros(m, dtype=np.int64)
    _scatter_theta(theta, packed, theta_loc, n_parts)
    stats.rho_fd_total = int(rounds.sum())
    stats.rho_fd_max = int(rounds.max()) if rounds.size else 0
    _record_fd_sharded(n_parts, rounds)
    return _finish(
        theta, part, ranges, sup_init, stats,
        dict(n_parts=n_parts, n_links=be.n_links, n_dev=int(n_dev)),
        return_result)


def _distributed_wing_csr(
    g: BipartiteGraph, mesh: Mesh, axis: str | Tuple[str, ...], P_parts: int,
    pair_aligned: bool = False, return_result: bool = False,
):
    """csr engine on a mesh: wedge-sharded CD + wedge-packed FD.

    ``pair_aligned`` swaps the round-robin wedge padding for the
    pair-aligned layout (one psum per CD round instead of two)."""
    wed = csr.build_wedges(g)
    m = g.m
    n_dev = int(mesh.devices.size)
    if pair_aligned:
        packed = shard_wedges_pair_aligned(wed, n_dev)
        round_fn = make_cd_round_csr_pair_aligned(
            mesh, axis, packed["Pmax"], m)
        placed, cd_shards = _place_sharded(
            mesh, axis, **{k: packed[k]
                           for k in ("alive", "W0", "we1", "we2", "wp")})
        pa_alive, pa_W = placed["alive"], placed["W0"]
        pa_we1, pa_we2, pa_wp = placed["we1"], placed["we2"], placed["wp"]
        sup0 = csr.edge_butterflies0(wed)
        if sup0.size and int(sup0.max()) > 2 ** 31 - 1:
            raise OverflowError(
                "wing supports exceed int32, the width of the wing "
                "path's device counts")
        support = jnp.asarray(sup0.astype(np.int32))
        st = None
    else:
        st = shard_wedges(wed, n_dev)
        placed, cd_shards = _place_sharded(
            mesh, axis, we1=st.we1, we2=st.we2, wp=st.wp, alive_w=st.alive_w)
        st = dataclasses.replace(st, **placed)
        round_fn = make_cd_round_csr(mesh, axis, st.n_pairs, m)
        support = st.support

    def step(active: np.ndarray) -> np.ndarray:
        nonlocal st, support, pa_alive, pa_W
        if pair_aligned:
            peeled_pad = jnp.concatenate(
                [jnp.asarray(active), jnp.zeros((1,), bool)])
            support_pad = jnp.concatenate(
                [support, jnp.zeros((1,), jnp.int32)])
            pa_alive, pa_W, support_pad = round_fn(
                peeled_pad, pa_alive, pa_W, support_pad,
                pa_we1, pa_we2, pa_wp)
            support = support_pad[:-1]
            return np.asarray(support).astype(np.int64)
        st = cd_round_sharded_csr(round_fn, st, jnp.asarray(active))
        return np.asarray(st.support).astype(np.int64)

    stats = PeelStats(engine="csr", fd_driver="device")
    sup0_np = np.asarray(support).astype(np.int64)
    spec = PeelSpec(
        kind="wing", n=m, sup0=sup0_np,
        workload=lambda s: np.maximum(s, 1), est=lambda s: s,
        cd_step=step,
    )
    with obs.span("cd", cat="cd"):
        part, sup_init, ranges, n_parts = cd_loop(
            spec, P_parts, stats,
            target=FixedTarget(float(sup0_np.sum()), P_parts))

    with obs.span("fd", cat="fd", driver="sharded") as sp:
        packed = pack_fd_partitions_csr(wed, part, sup_init, n_parts)
        theta_loc, rounds = fd_peel_sharded_csr(packed, mesh, axis)
        if sp is not None:
            sp.update(rounds=int(rounds.sum()))
    theta = np.zeros(m, dtype=np.int64)
    _scatter_theta(theta, packed, theta_loc, n_parts)
    stats.rho_fd_total = int(rounds.sum())
    stats.rho_fd_max = int(rounds.max()) if rounds.size else 0
    _record_fd_sharded(n_parts, rounds)
    return _finish(
        theta, part, ranges, sup_init, stats,
        dict(cd_sharding="pair_aligned" if pair_aligned else "wedge",
             n_parts=n_parts, n_wedges=wed.n_wedges,
             n_pairs=wed.n_pairs, n_dev=n_dev, cd_shards=cd_shards),
        return_result)


# =====================================================================
# Distributed TIP decomposition (vertex peeling, §3.2)
# =====================================================================
def make_tip_cd_recount(mesh: Mesh, axis: str | Tuple[str, ...], n: int, n_dev: int):
    """Jitted row-sharded tip batch re-count; returns (fn, rows/shard).

    The dense-engine fallback: shard the *row blocks* of the wedge
    matrix across devices; each device re-counts butterflies for its
    vertex shard (A gathered per round — O(n²) work and memory, which
    is exactly why ``engine="csr"`` is the default)."""
    blk = -(-n // n_dev)

    def body(A_pad, alive_pad, shard_idx):
        # per-shard: A_pad [blk, nv], alive [blk], idx [1]
        row0 = shard_idx[0] * blk
        A_full = jax.lax.all_gather(A_pad, axis, axis=0, tiled=True)
        alive_full = jax.lax.all_gather(alive_pad, axis, axis=0, tiled=True)
        Am = A_full * alive_full[:, None]
        W = jax.lax.dot(A_pad * alive_pad[:, None], Am.T,
                        precision=jax.lax.Precision.HIGHEST)
        rows = row0 + jnp.arange(A_pad.shape[0])
        cols = jnp.arange(A_full.shape[0])
        W = jnp.where(rows[:, None] == cols[None, :], 0.0, W)
        return jnp.sum(W * (W - 1.0) * 0.5, axis=1)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis),
    )
    return jax.jit(fn), blk


def _tip_fd_kernel(A_i, mine, sup0):
    """Peel one dense tip partition bottom-up — the shared device FD
    driver with the static pairwise-butterfly matvec update: one
    while_loop, no collectives.

    A_i: [Umax, nv] rows of this partition (zero-padded), mine [Umax],
    sup0 [Umax].  Pairwise butterflies are static (V never peeled)."""
    W = jax.lax.dot(A_i, A_i.T, precision=jax.lax.Precision.HIGHEST)
    Umax = W.shape[0]
    W = W * (1.0 - jnp.eye(Umax, dtype=W.dtype))
    pair_bf = W * (W - 1.0) * 0.5

    def update(S, aux):
        loss = jnp.rint(pair_bf @ S.astype(jnp.float32)).astype(jnp.int32)
        return loss, aux, jnp.int32(0)

    theta, rounds, _ = _fd_while_device(
        mine, jnp.rint(sup0).astype(jnp.int32), update, jnp.int32(0))
    return theta, rounds


@_with_obs("tip")
def distributed_tip_decomposition(
    g: BipartiteGraph,
    mesh: Mesh,
    axis: str | Tuple[str, ...] = "peel",
    side: str = "u",
    P_parts: int = 8,
    engine: str = "csr",
    aligned: bool = False,
    fd_driver: str = "device",
    return_result: bool = False,
):
    """Full PBNG tip decomposition on a device mesh.

    ``engine="csr"`` (default): wedge-list CD — the directed
    pair-incidence list is sharded (``aligned=True`` keeps ALL of a
    vertex's entries on one device via the generalized greedy balance)
    and every round pays exactly ONE psum (pair butterflies are static,
    so there is no dying-count collective at all); FD stacks the
    disjoint per-partition pair lists and peels under ``shard_map`` with
    zero collectives (``fd_driver="device"``), or in ONE batched
    single-dispatch while_loop (``fd_driver="vmapped"``).  O(Σ deg²)
    memory end to end — the path that opens the largest-graph tip
    workloads.

    ``engine="dense"``: the explicit O(n²) fallback — row-sharded
    masked-matmul re-counts for CD, stacked matmul-cascade partitions
    for FD.  Kept for machines where the wedge list is the bigger
    allocation (near-complete bipartite cores); refuses nothing but
    memory.

    θ is bit-identical across both engines and to the single-device
    oracle.  Returns ``(theta, stats)``; ``return_result=True`` appends
    the full :class:`~repro.core.peelspec.PeelResult` (partition
    provenance for the hierarchy serializer).

    Example (8 forced host devices)::

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        theta, stats = distributed_tip_decomposition(
            g, mesh, side="u", engine="csr", aligned=True)
    """
    if engine not in ("csr", "dense"):
        raise ValueError(engine)
    if fd_driver not in ("device", "vmapped"):
        raise ValueError(fd_driver)
    if engine == "dense" and (aligned or fd_driver != "device"):
        raise ValueError(
            "aligned / fd_driver='vmapped' need the wedge list: "
            "engine='csr' only")
    gg = g if side == "u" else g.transpose()
    if engine == "csr":
        return _distributed_tip_csr(
            gg, mesh, axis, side, P_parts, aligned=aligned,
            fd_driver=fd_driver, return_result=return_result)
    return _distributed_tip_dense(
        gg, mesh, axis, side, P_parts, return_result=return_result)


def _distributed_tip_csr(
    gg: BipartiteGraph, mesh: Mesh, axis: str | Tuple[str, ...], side: str, P_parts: int,
    aligned: bool = False, fd_driver: str = "device",
    return_result: bool = False,
):
    """csr tip on a mesh: one-psum pair-incidence CD + stacked pair FD."""
    wed = csr.build_wedges(gg)
    n = gg.n_u
    n_dev = int(mesh.devices.size)
    pair_bf0 = wed.pair_butterflies0()
    sup0 = csr.vertex_butterflies_csr(wed)
    if sup0.size and int(sup0.max()) > 2 ** 31 - 1:
        raise OverflowError(
            "tip supports exceed int32, the width of the sharded tip "
            "path's counts; the single-device csr engine widens them")
    wu, _ = csr.wedge_workload(gg)
    wedge_w = wu.astype(np.float64)

    blocks = shard_tip_pairs(wed, pair_bf0, n_dev, aligned=aligned)
    round_fn = make_cd_round_tip_csr(mesh, axis, n)
    placed, cd_shards = _place_sharded(
        mesh, axis, **{k: blocks[k] for k in ("dst", "src", "bf")})
    dst, src, bf = placed["dst"], placed["src"], placed["bf"]
    state = dict(support=jnp.asarray(sup0.astype(np.int32)))

    def step(active: np.ndarray) -> np.ndarray:
        peeled_pad = jnp.concatenate(
            [jnp.asarray(active), jnp.zeros((1,), bool)])
        support_pad = jnp.concatenate(
            [state["support"], jnp.zeros((1,), jnp.int32)])
        support_pad = round_fn(peeled_pad, support_pad, dst, src, bf)
        state["support"] = support_pad[:-1]
        return np.asarray(state["support"]).astype(np.int64)

    stats = PeelStats(engine="csr", fd_driver=fd_driver, side=side)
    # same ≥1 workload clamp as the dense distributed path so the two
    # engines pick identical range boundaries (stats comparability)
    spec = PeelSpec(
        kind="tip", n=n, sup0=sup0,
        workload=lambda s: np.maximum(wedge_w, 1),
        est=lambda s: wedge_w,
        cd_step=step,
    )
    with obs.span("cd", cat="cd"):
        part, sup_init, ranges, n_parts = cd_loop(
            spec, P_parts, stats,
            target=FixedTarget(float(wedge_w.sum()), P_parts))

    theta = np.zeros(n, dtype=np.int64)
    if n_parts:
        with obs.span("fd", cat="fd", driver=fd_driver) as sp:
            if fd_driver == "vmapped":
                from .peel import _tip_fd_vmapped_csr

                # the vmapped wrapper drains its own counter rings
                rounds = _tip_fd_vmapped_csr(
                    wed, pair_bf0, part, sup_init, theta, n_parts)
            else:
                packed = pack_fd_partitions_tip_csr(
                    wed, pair_bf0, part, sup_init, n_parts, stacked=True)
                theta_loc, rounds = fd_peel_sharded_tip_csr(
                    packed, mesh, axis)
                _scatter_theta(theta, packed, theta_loc, n_parts)
                _record_fd_sharded(n_parts, rounds)
            if sp is not None:
                sp.update(rounds=int(np.asarray(rounds).sum()))
        stats.rho_fd_total = int(np.asarray(rounds).sum())
        stats.rho_fd_max = int(np.asarray(rounds).max())
    return _finish(
        theta, part, ranges, sup_init, stats,
        dict(cd_sharding="vertex_aligned" if aligned else "pair",
             n_parts=n_parts, n_wedges=wed.n_wedges,
             n_pairs=wed.n_pairs, n_dev=n_dev, cd_shards=cd_shards),
        return_result)


def _distributed_tip_dense(
    gg: BipartiteGraph, mesh: Mesh, axis: str | Tuple[str, ...], side: str, P_parts: int,
    return_result: bool = False,
):
    """Dense tip on a mesh: row-sharded masked-matmul re-counts for CD,
    stacked matmul-cascade partitions for FD — the explicit O(n²)
    fallback behind ``engine="dense"``."""
    from . import counting

    n, nv = gg.n_u, gg.n_v
    n_dev = int(mesh.devices.size)
    A_np = gg.adjacency()
    recount_fn, blk = make_tip_cd_recount(mesh, axis, n, n_dev)
    n_pad = blk * n_dev
    A = jnp.asarray(np.pad(A_np, ((0, n_pad - n), (0, 0))))
    shard_idx = jnp.arange(n_dev, dtype=jnp.int32)

    alive_pad = np.ones(n_pad, bool)
    alive_pad[n:] = False
    sup0 = np.rint(np.asarray(
        recount_fn(A, jnp.asarray(alive_pad), shard_idx))).astype(
            np.int64)[:n]
    wedge_w = np.rint(np.asarray(
        counting.vertex_wedge_workload(jnp.asarray(A_np)))).astype(np.int64)

    def step(active: np.ndarray) -> np.ndarray:
        alive_pad[:n] &= ~active
        sup = np.rint(np.asarray(recount_fn(
            A, jnp.asarray(alive_pad), shard_idx))).astype(np.int64)
        return sup[:n]

    stats = PeelStats(engine="dense", fd_driver="device", side=side)
    # range-selection weights clamp to ≥1 (as pre-refactor) so
    # zero-wedge vertices still advance the cumulative-workload scan
    spec = PeelSpec(
        kind="tip", n=n, sup0=sup0,
        workload=lambda s: np.maximum(wedge_w, 1),
        est=lambda s: wedge_w,
        cd_step=step,
    )
    with obs.span("cd", cat="cd"):
        part, sup_init, ranges, n_parts = cd_loop(
            spec, P_parts, stats,
            target=FixedTarget(float(wedge_w.sum()), P_parts))

    # ---- FD: stack padded partitions, shard over devices
    rows_per = [np.where(part == i)[0] for i in range(n_parts)]
    Umax = max(max((r.size for r in rows_per), default=1), 1)
    pad_parts = -(-max(n_parts, 1) // n_dev) * n_dev
    A_st = np.zeros((pad_parts, Umax, nv), np.float32)
    mine = np.zeros((pad_parts, Umax), bool)
    sup_st = np.zeros((pad_parts, Umax), np.float32)
    gids = np.zeros((pad_parts, Umax), np.int64)
    for i, r in enumerate(rows_per):
        A_st[i, : r.size] = A_np[r]
        mine[i, : r.size] = True
        sup_st[i, : r.size] = sup_init[r]
        gids[i, : r.size] = r
    vk = jax.vmap(_tip_fd_kernel)
    fd = shard_map(
        vk, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
    )
    with obs.span("fd", cat="fd", driver="sharded") as sp:
        theta_st, rounds = jax.jit(fd)(
            jnp.asarray(A_st), jnp.asarray(mine), jnp.asarray(sup_st))
        if sp is not None:
            sp.update(rounds=int(np.asarray(rounds)[:n_parts].sum()))
    theta_st = np.asarray(theta_st).astype(np.int64)
    theta = np.zeros(n, np.int64)
    _scatter_theta(theta, dict(mine=mine, gids=gids), theta_st, n_parts)
    rounds = np.asarray(rounds)[:n_parts]
    stats.rho_fd_total = int(rounds.sum())
    stats.rho_fd_max = int(rounds.max()) if n_parts else 0
    _record_fd_sharded(n_parts, rounds)
    return _finish(
        theta, part, ranges, sup_init, stats,
        dict(n_parts=n_parts, n_dev=n_dev),
        return_result)
