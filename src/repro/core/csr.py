"""Sparse CSR peeling engine — wedge-list butterfly machinery (alg.1 on TPU).

The ``dense`` engine materializes the full n_u×n_v adjacency (and an
n_u×n_u wedge matrix) for every batch re-count, capping graph size at
O(n²) memory long before butterfly workload matters.  This module is the
O(Σ deg²) alternative used by ``engine="csr"``: ParButterfly/RECEIPT-style
wedge enumeration, expressed with static shapes so XLA can compile it.

Pipeline:
  1. Host side (numpy, vectorized): flatten the graph's V-side CSR into a
     **wedge list** — every pair of edges sharing a V center.  Wedges with
     the same U-endpoint pair {a, b} are grouped under one *pair id*; a
     butterfly is exactly two wedges of the same pair, so all counting
     reduces to per-pair wedge counts W_p:

         pair butterflies       = C(W_p, 2)
         ⋈_u (vertex support)   = Σ_{p ∋ u} C(W_p, 2)
         ⋈_e (edge support)     = Σ_{wedges w ∋ e} (W_{p(w)} − 1)

  2. Device side: all counts are ``jax.ops.segment_sum`` over the flat
     wedge list; peeling updates are *incremental* — only butterflies
     incident to peeled entities are recomputed (the BE-Index widow /
     survivor algebra with pairs playing the role of blooms).

  3. Optionally, the per-pair reduction runs through the blocked Pallas
     kernel in ``repro.kernels.wedge_count`` over a :class:`PaddedCSR`
     pairs-major slot matrix (MXU/VMEM tiling; interpret mode on CPU).

Everything is exact integer arithmetic — int32 on device, int64 where a
tip graph's counts exceed int32 (:func:`count_bits`, :func:`count_scope`)
— no f32 rounding, so θ from the csr engine is bit-identical to the BUP
oracle.  Tip peeling reads only the U pairs with a common neighbour and
their counts (:class:`TipPairs`); where it pays, one int8 adjacency
product on the MXU gives them instead of the wedge list
(:func:`tip_pairs`).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .graph import BipartiteGraph

__all__ = [
    "Wedges",
    "TipPairs",
    "PaddedCSR",
    "TileStats",
    "build_wedges",
    "tip_pairs",
    "tip_pair_adjacency",
    "tip_pair_counts",
    "pairs_from_product",
    "count_bits",
    "count_scope",
    "iter_wedge_tiles",
    "tiled_butterfly_init",
    "pad_segments",
    "pack_wedge_slots",
    "directed_pair_incidence",
    "pack_tip_slots",
    "pack_update_slots",
    "wedge_workload",
    "pair_wedge_counts",
    "vertex_butterflies_csr",
    "edge_butterflies_csr",
    "total_butterflies_csr",
    "tip_delta_csr",
    "tip_delta_slots",
    "wing_loss_csr",
    "wing_update_csr",
    "wing_update_slots",
]

_INT_LIMIT = 2 ** 31 - 1  # device counts are int32; guard exactness


# =====================================================================
# Host-side construction
# =====================================================================
@dataclasses.dataclass(frozen=True)
class Wedges:
    """Flattened wedge list of a bipartite graph (centers on the V side).

    A wedge is an ordered triple (u_a, v, u_b) with u_a < u_b; it is
    stored as its two edge ids plus the id of its U-endpoint *pair*.
    All arrays are host numpy; engines move them to device once.
    """

    n_u: int
    n_v: int
    m: int
    n_pairs: int
    pair_a: np.ndarray      # (n_pairs,) int32 — smaller U endpoint
    pair_b: np.ndarray      # (n_pairs,) int32 — larger U endpoint
    wedge_pair: np.ndarray  # (n_wedges,) int32 — pair id per wedge
    wedge_e1: np.ndarray    # (n_wedges,) int32 — edge (pair_a, center)
    wedge_e2: np.ndarray    # (n_wedges,) int32 — edge (pair_b, center)
    W0: np.ndarray          # (n_pairs,) int64 — static full-graph wedge count

    @property
    def n_wedges(self) -> int:
        """Number of enumerated wedges (= Σ_v C(d_v, 2))."""
        return int(self.wedge_pair.shape[0])

    def pair_butterflies0(self) -> np.ndarray:
        """Static C(W0, 2) per pair (V side never peeled ⇒ valid for tip),
        exact int64."""
        w = self.W0
        return w * (w - 1) // 2


@dataclasses.dataclass(frozen=True)
class TipPairs:
    """Every pair of U vertices with a common V neighbour, and how many
    they share: all tip peeling reads of the graph.

    ``source`` says how the list was made: ``"wedges"`` from the host
    wedge list (:func:`build_wedges`), ``"mxu"`` from one adjacency
    product on the device (:func:`pairs_from_product`), whose shape as
    it ran, padding included, is ``rows`` × ``k`` of ``dtype``.  Pairs
    are ordered by (pair_a, pair_b) either way, so both sources give
    the same arrays."""

    n_u: int
    pair_a: np.ndarray      # (n_pairs,) int32 — smaller U endpoint
    pair_b: np.ndarray      # (n_pairs,) int32 — larger U endpoint
    W0: np.ndarray          # (n_pairs,) int64 — common neighbours
    source: str = "wedges"
    rows: int = 0
    k: int = 0
    dtype: str = ""

    @property
    def n_pairs(self) -> int:
        """Number of pairs with a common neighbour."""
        return int(self.pair_a.shape[0])

    pair_butterflies0 = Wedges.pair_butterflies0

    @classmethod
    def of(cls, w: Wedges) -> "TipPairs":
        """The pair part of a wedge list."""
        return cls(w.n_u, w.pair_a, w.pair_b, w.W0)


def build_wedges(g: BipartiteGraph) -> Wedges:
    """Enumerate every wedge (V center, U endpoints) — vectorized numpy.

    Work and memory are O(Σ_v C(d_v, 2)); no n² anywhere.  Neighbor lists
    in ``csr_v`` are u-sorted, so pair endpoints come out ordered.
    """
    off, nbr, eid = g.csr_v()
    deg = np.diff(off)
    pos = np.arange(nbr.size, dtype=np.int64)
    center = np.repeat(np.arange(g.n_v, dtype=np.int64), deg)
    # position p pairs with every later position of the same center
    row_len = off[center + 1] - pos - 1 if nbr.size else np.zeros(0, np.int64)
    total = int(row_len.sum()) if nbr.size else 0
    if total == 0:
        empty32 = np.zeros(0, dtype=np.int32)
        return Wedges(
            n_u=g.n_u, n_v=g.n_v, m=g.m, n_pairs=0,
            pair_a=empty32, pair_b=empty32, wedge_pair=empty32,
            wedge_e1=empty32, wedge_e2=empty32,
            W0=np.zeros(0, dtype=np.int64),
        )
    e1_pos = np.repeat(pos, row_len)
    starts = np.cumsum(row_len) - row_len
    k = np.arange(total, dtype=np.int64) - np.repeat(starts, row_len)
    e2_pos = e1_pos + 1 + k
    a = nbr[e1_pos].astype(np.int64)
    b = nbr[e2_pos].astype(np.int64)
    key = a * g.n_u + b
    pair_key, wedge_pair = np.unique(key, return_inverse=True)
    if pair_key.size > _INT_LIMIT:
        raise OverflowError("pair count exceeds int32 range")
    return Wedges(
        n_u=g.n_u, n_v=g.n_v, m=g.m, n_pairs=int(pair_key.size),
        pair_a=(pair_key // g.n_u).astype(np.int32),
        pair_b=(pair_key % g.n_u).astype(np.int32),
        wedge_pair=wedge_pair.astype(np.int32),
        wedge_e1=eid[e1_pos].astype(np.int32),
        wedge_e2=eid[e2_pos].astype(np.int32),
        W0=np.bincount(wedge_pair, minlength=pair_key.size).astype(np.int64),
    )


# =====================================================================
# Tip pair counts: the host wedge list or one MXU product
# =====================================================================
# The product replaces the wedge enumeration where it fits and costs
# less.  It fits where its int8 operand and int32 result stay under
# 512 MiB and 256 MiB (the operand bound also keeps its padded flat
# indices below 2^31).  Its cost is a fixed part (two launches, the
# index upload, the readback's set-up), rows² counts read back and
# scanned on the host, and rows²·k multiply-adds on the device; the
# host's is one pass per wedge.  In ns: the host rates timed on an x86
# host, the multiply-add the v5e compiler's estimate for the hub
# product, 755,548 cycles at 1.5 GHz for 512²·2^18 (PERF.md §6).
_MXU_OPERAND_BYTES = 2 ** 29
_MXU_RESULT_BYTES = 2 ** 28
_NS_FIXED = 2e6
_NS_PER_COUNT = 8.0
_NS_PER_MAC = 0.0073
_NS_PER_WEDGE = 100.0


def _product_shape(n_u: int, n_v: int) -> Tuple[int, int]:
    """(rows, k) of the padded U × V adjacency the product reads: rows
    a multiple of 128, k a quarter-power-of-two bucket (≤ 25 % padding),
    so graphs of similar size share one compiled program."""
    from .peelspec import _bucket_pad

    return -(-max(n_u, 1) // 128) * 128, _bucket_pad(max(n_v, 1))


def pair_source(n_u: int, n_v: int, wedges: int) -> str:
    """``"mxu"`` or ``"wedges"``: how :func:`tip_pairs` builds the pair
    list of a graph with ``n_u`` peeled vertices, ``n_v`` on the other
    side and ``wedges`` = Σ_v C(d_v, 2) (the rule above)."""
    rows, k = _product_shape(n_u, n_v)
    fits = (rows * k <= _MXU_OPERAND_BYTES
            and rows * rows * 4 <= _MXU_RESULT_BYTES)
    cost = _NS_FIXED + rows * rows * (_NS_PER_COUNT + k * _NS_PER_MAC)
    return "mxu" if fits and cost <= wedges * _NS_PER_WEDGE else "wedges"


@partial(jax.jit, static_argnames=("rows", "k"))
def tip_pair_adjacency(flat: jax.Array, rows: int, k: int):
    """The (rows, k) int8 0/1 adjacency the product reads: the edges, as
    sorted distinct flat indices u·k + v (padding past rows·k, dropped),
    set in zeros.  The scatter takes its indices with their order
    declared, so the compiler adds no sort and every operation it emits
    carries this program's name."""
    return jnp.zeros((rows * k,), jnp.int8).at[flat].set(
        jnp.int8(1), mode="drop", indices_are_sorted=True,
        unique_indices=True).reshape(rows, k)


@jax.jit
def tip_pair_counts(adj: jax.Array):
    """Common neighbours of every pair of U rows: the int8 adjacency
    times its transpose on the MXU with int32 accumulation, and nothing
    else, so the device trace names the product alone.  Exact: an entry
    counts at most k neighbours, and :func:`pair_source` keeps
    rows·k < 2^31."""
    return jax.lax.dot_general(
        adj, adj, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)


def pairs_from_product(g: BipartiteGraph) -> TipPairs:
    """The pair list of g from two programs on the device,
    :func:`tip_pair_adjacency` over its edges and :func:`tip_pair_counts`
    over that; the host keeps the upper triangle's non-zero entries, in
    (pair_a, pair_b) order as :func:`build_wedges` gives them."""
    from .peelspec import _bucket_pad

    rows, k = _product_shape(g.n_u, g.n_v)
    flat = g.edges[:, 0].astype(np.int64) * k + g.edges[:, 1]
    if not (np.diff(flat) > 0).all():      # the scatter's declared order
        flat = np.unique(flat)
    idx = np.arange(rows * k, rows * k + _bucket_pad(flat.size),
                    dtype=np.int64)
    idx[:flat.size] = flat
    adj = tip_pair_adjacency(jnp.asarray(idx.astype(np.int32)),
                             rows=rows, k=k)
    c = np.asarray(tip_pair_counts(adj))[:g.n_u, :g.n_u]
    a, b = np.nonzero(np.triu(c, 1))
    return TipPairs(g.n_u, a.astype(np.int32), b.astype(np.int32),
                    c[a, b].astype(np.int64), source="mxu", rows=rows, k=k,
                    dtype="int8")


def tip_pairs(g: BipartiteGraph) -> TipPairs:
    """The pair list tip peeling of g's U side reads, from the source
    :func:`pair_source` picks out of the degrees."""
    _, dv = g.degrees()
    wedges = int((dv * (dv - 1) // 2).sum())
    if pair_source(g.n_u, g.n_v, wedges) == "mxu":
        return pairs_from_product(g)
    return TipPairs.of(build_wedges(g))


def count_bits(*counts: np.ndarray) -> int:
    """32, or 64 where any of the int64 ``counts`` exceeds int32: the
    device width the peel's supports, deltas and θ need.  Supports only
    fall during peeling, so their initial values decide."""
    top = max((int(c.max()) for c in counts if c.size), default=0)
    return 64 if top > _INT_LIMIT else 32


def count_scope(bits: int):
    """Where device counts of ``bits`` are made and used: JAX's scoped
    64-bit mode for 64 (its programs are traced and cached apart), no
    change for 32, so the int32 programs stay as they are."""
    return jax.enable_x64(True) if bits == 64 else contextlib.nullcontext()


# =====================================================================
# Bounded-tile wedge enumeration + ⋈init (the out-of-core counting path)
# =====================================================================
@dataclasses.dataclass
class TileStats:
    """What the tiled ⋈init actually did — feeds the obs counter and the
    peak-memory bench rows (``count.real.*``)."""

    n_tiles: int = 0
    n_wedges: int = 0          # Σ over tiles (== untiled wedge count)
    n_pairs: int = 0           # Σ distinct pairs (tiles don't split pairs)
    peak_tile_wedges: int = 0  # largest single tile
    peak_slot_bytes: int = 0   # largest Pallas slot matrix (0 = host path)


def iter_wedge_tiles(source, tile_wedges: int = 1 << 20):
    """Yield wedge batches ``(a, b, e1, e2)`` of ≈ ``tile_wedges`` each.

    The full wedge list is O(Σ_v C(d_v, 2)) — the memory blocker for
    real graphs.  This generator never materializes it: wedges are
    grouped by their **smaller U endpoint** ``a`` (neighbor lists in
    ``csr_v`` are u-sorted, so position p wedges with every later
    position of its center — all of them have ``a = nbr[p]``), and a
    tile covers a contiguous U range chosen greedily from the exact
    per-vertex wedge counts.  Because every wedge of pair {a, b} shares
    the same minimum endpoint, each pair's wedges land in exactly one
    tile — per-tile pair counts are globally complete, which is what
    makes :func:`tiled_butterfly_init` bit-identical to the untiled
    path.  A hub vertex whose own wedge count exceeds ``tile_wedges``
    becomes a tile by itself (peak = max(tile_wedges, max per-vertex
    count)); vertex-level splitting isn't needed below that.

    ``source`` is anything with ``n_u``/``n_v``/``m`` and ``csr_v()``
    (``BipartiteGraph`` or ``data.ingest.IngestedGraph`` — the latter
    memory-maps its CSR, so the graph itself stays on disk).
    """
    off, nbr, eid = source.csr_v()
    n_u = source.n_u
    if nbr.size == 0:
        return
    deg = np.diff(off)
    pos = np.arange(nbr.size, dtype=np.int64)
    center = np.repeat(np.arange(source.n_v, dtype=np.int64), deg)
    tail = (off[center + 1] - pos - 1).astype(np.int64)
    # exact wedge count per minimum endpoint, and V-CSR positions
    # grouped by that endpoint (stable sort keeps center order)
    w_u = np.bincount(nbr, weights=tail, minlength=n_u).astype(np.int64)
    by_u = np.argsort(nbr, kind="stable")
    eoff = np.zeros(n_u + 1, dtype=np.int64)
    np.cumsum(np.bincount(nbr, minlength=n_u), out=eoff[1:])
    cw = np.cumsum(w_u)
    u0 = 0
    base = 0
    while u0 < n_u:
        u1 = int(np.searchsorted(cw, base + tile_wedges, side="right"))
        u1 = min(max(u1, u0 + 1), n_u)
        base = int(cw[u1 - 1])
        P = by_u[eoff[u0]:eoff[u1]]
        u0 = u1
        t = tail[P]
        total = int(t.sum())
        if total == 0:
            continue
        e1_pos = np.repeat(P, t)
        starts = np.cumsum(t) - t
        k = np.arange(total, dtype=np.int64) - np.repeat(starts, t)
        e2_pos = e1_pos + 1 + k
        yield (
            nbr[e1_pos].astype(np.int64),
            nbr[e2_pos].astype(np.int64),
            eid[e1_pos].astype(np.int64),
            eid[e2_pos].astype(np.int64),
        )


def tiled_butterfly_init(
    source,
    tile_wedges: int = 1 << 20,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
    width: int = 512,
) -> Tuple[np.ndarray, np.ndarray, int, TileStats]:
    """⋈init under bounded memory: (sup_e, sup_u, total, stats).

    Streams :func:`iter_wedge_tiles` and reduces each tile to per-pair
    wedge counts — peak host memory is O(tile), peak device memory one
    Pallas block, never O(Σ deg²).  All accumulation is exact integer
    arithmetic: per-tile counts (int32 Pallas row partials of ≤ ``width``
    flags each, or a host ``diff``), reduced into int64 on the host — so
    there is **no** 2²⁴ ceiling here, and the outputs are bit-identical
    to :func:`edge_butterflies0` / :func:`vertex_butterflies_csr` /
    :func:`total_butterflies_csr` (integer addition commutes).

    With ``use_pallas`` each tile's count runs through the blocked
    tile-accumulate kernel (``kernels.wedge_count
    .wedge_count_tile_pallas``): pairs are laid out as fixed-``width``
    slot rows, hub pairs split across several rows whose int32 partials
    (each ≤ ``width``) are summed per pair in int64.
    """
    from repro import obs  # local import: keep core importable without obs

    n_u, m = source.n_u, source.m
    sup_e = np.zeros(m, dtype=np.int64)
    sup_u = np.zeros(n_u, dtype=np.int64)
    total = 0
    stats = TileStats()
    if use_pallas:
        from repro.kernels import ops as kops
        if interpret is None:
            interpret = kops.default_interpret()
    for a, b, e1, e2 in iter_wedge_tiles(source, tile_wedges):
        nk = a.size
        key = a * n_u + b
        order = np.argsort(key, kind="stable")
        ks = key[order]
        e1s = e1[order]
        e2s = e2[order]
        newp = np.empty(nk, dtype=bool)
        newp[0] = True
        np.not_equal(ks[1:], ks[:-1], out=newp[1:])
        starts_p = np.flatnonzero(newp)
        n_pairs_t = starts_p.size
        pid = np.cumsum(newp) - 1
        cnt = np.diff(np.append(starts_p, nk))
        if use_pallas:
            within = np.arange(nk, dtype=np.int64) - starts_p[pid]
            rows_per_pair = -(-cnt // width)
            row_base = np.cumsum(rows_per_pair) - rows_per_pair
            rowid = row_base[pid] + within // width
            col = within % width
            n_rows = int(rows_per_pair.sum())
            slots = np.zeros((n_rows, width), dtype=np.int32)
            slots[rowid, col] = 1
            stats.peak_slot_bytes = max(stats.peak_slot_bytes, slots.nbytes)
            row_sums = kops.tile_row_counts(slots, interpret=interpret)
            W = np.zeros(n_pairs_t, dtype=np.int64)
            row_to_pair = np.repeat(
                np.arange(n_pairs_t, dtype=np.int64), rows_per_pair
            )
            np.add.at(W, row_to_pair, row_sums.astype(np.int64))
        else:
            W = cnt.astype(np.int64)
        bf = W * (W - 1) // 2
        pa = ks[starts_p] // n_u
        pb = ks[starts_p] % n_u
        np.add.at(sup_u, pa, bf)
        np.add.at(sup_u, pb, bf)
        total += int(bf.sum())
        contrib = W[pid] - 1
        np.add.at(sup_e, e1s, contrib)
        np.add.at(sup_e, e2s, contrib)
        stats.n_tiles += 1
        stats.n_wedges += nk
        stats.n_pairs += n_pairs_t
        stats.peak_tile_wedges = max(stats.peak_tile_wedges, nk)
    obs.counter("counting.tiles", dict(
        tiles=stats.n_tiles, wedges=stats.n_wedges, pairs=stats.n_pairs,
        peak_tile_wedges=stats.peak_tile_wedges,
        peak_slot_bytes=stats.peak_slot_bytes,
    ))
    return sup_e, sup_u, total, stats


def wedge_workload(g: BipartiteGraph) -> Tuple[np.ndarray, np.ndarray]:
    """Paper's range-selection workload proxy Σ_{v∈N_u} d_v, per side.

    Dense engine computes this as A @ d_v; here it is two bincounts."""
    du, dv = g.degrees()
    if g.m == 0:
        return np.zeros(g.n_u, np.int64), np.zeros(g.n_v, np.int64)
    wu = np.bincount(g.edges[:, 0], weights=dv[g.edges[:, 1]], minlength=g.n_u)
    wv = np.bincount(g.edges[:, 1], weights=du[g.edges[:, 0]], minlength=g.n_v)
    return wu.astype(np.int64), wv.astype(np.int64)


# =====================================================================
# Padded-CSR device representation (pairs-major slots for the kernel)
# =====================================================================
@dataclasses.dataclass(frozen=True)
class PaddedCSR:
    """Row-padded CSR block: row r holds segment r's items, −1 padded.

    The device-friendly face of a ragged grouping — rows padded to a
    sublane multiple, width to a lane multiple, so Pallas kernels can
    tile it straight into VMEM.
    """

    n_rows: int             # real segment count
    n_rows_pad: int         # rows after sublane padding
    width: int              # slots per row (lane multiple)
    idx: np.ndarray         # (n_rows_pad, width) int32, −1 = padding
    valid: np.ndarray       # (n_rows_pad, width) bool


def pad_segments(
    seg_ids: np.ndarray,
    n_rows: int,
    row_mult: int = 8,
    lane_mult: int = 128,
) -> PaddedCSR:
    """Pack item → segment assignments into a :class:`PaddedCSR`.

    ``idx[r, c]`` is the original item index of segment r's c-th member.
    """
    counts = np.bincount(seg_ids, minlength=max(n_rows, 1))
    width = max(int(counts.max()) if counts.size else 1, 1)
    width = -(-width // lane_mult) * lane_mult
    n_rows_pad = -(-max(n_rows, 1) // row_mult) * row_mult
    idx = np.full((n_rows_pad, width), -1, dtype=np.int32)
    valid = np.zeros((n_rows_pad, width), dtype=bool)
    if seg_ids.size:
        order = np.argsort(seg_ids, kind="stable")
        sorted_ids = seg_ids[order]
        off = np.zeros(n_rows + 1, dtype=np.int64)
        np.cumsum(counts[:n_rows], out=off[1:])
        col = np.arange(seg_ids.size, dtype=np.int64) - off[sorted_ids]
        idx[sorted_ids, col] = order.astype(np.int32)
        valid[sorted_ids, col] = True
    return PaddedCSR(
        n_rows=n_rows, n_rows_pad=n_rows_pad, width=width, idx=idx, valid=valid
    )


def pack_wedge_slots(w: Wedges) -> PaddedCSR:
    """Pairs-major wedge slots: row p lists pair p's wedge indices."""
    return pad_segments(w.wedge_pair, w.n_pairs)


def directed_pair_incidence(
    w: Wedges, pair_bf0: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Directed pair-incidence triple ``(dst, src, bf)`` — each pair
    {a, b} as two entries (dst=a, src=b) and (dst=b, src=a) carrying
    the static butterfly count.  THE tip-CD layout convention, shared
    by the vertex-major Pallas slots (:func:`pack_tip_slots`) and the
    distributed CD shards (``distributed.shard_tip_pairs``): vertex
    dst loses bf when src peels."""
    dst = np.concatenate([w.pair_a, w.pair_b]).astype(np.int64)
    src = np.concatenate([w.pair_b, w.pair_a]).astype(np.int64)
    val = np.concatenate([pair_bf0, pair_bf0]).astype(np.int32)
    return dst, src, val


def pack_tip_slots(
    w: Wedges, pair_bf0: np.ndarray, sup: Optional[np.ndarray] = None
) -> dict:
    """Vertex-major pair slots for the tip Pallas CD path.

    Row u lists vertex u's incident pairs as directed entries: each pair
    {a, b} appears twice — once in row a with partner b, once in row b
    with partner a — so a peel round's delta for u is the row sum of
    pair butterflies whose partner was peeled (``kernels.ops
    .tip_slot_loss``; rows ARE vertices, so no scatter back).  ``bf`` is
    0 on padding slots (algebra-neutral), ``partner`` the sentinel n.

    Per-row sums are bounded by the vertex's ⋈ support; past 2²⁴ those
    stop being exact f32 integers, so refuse up front like
    :func:`pack_update_slots` (supports only decrease — checking ⋈init
    once is sufficient).  Pass the caller's precomputed ⋈init as
    ``sup`` to skip recomputing it for the guard."""
    n = w.n_u
    if sup is None:
        sup = vertex_butterflies_csr(w)
    if sup.size and int(sup.max()) >= 2 ** 24:
        raise OverflowError(
            "tip supports exceed f32 integer range (2^24); "
            "use the segment_sum path (use_pallas=False)"
        )
    dst, src, val = directed_pair_incidence(w, pair_bf0)
    packed = pad_segments(dst, n)
    partner = np.full(packed.idx.shape, n, dtype=np.int32)
    bf = np.zeros(packed.idx.shape, dtype=np.int32)
    if dst.size:
        idx = np.maximum(packed.idx, 0)
        partner = np.where(packed.valid, src[idx], n).astype(np.int32)
        bf = np.where(packed.valid, val[idx], 0).astype(np.int32)
    return dict(partner=partner, bf=bf, n=n)


def tip_delta_slots(
    peeled_u: jax.Array,       # (n,) bool — U vertices peeled this round
    slot_partner: jax.Array,   # (n_rows_pad, K) int32, sentinel n
    slot_bf: jax.Array,        # (n_rows_pad, K) int32, 0 on padding
    n: int,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Pallas-kernel variant of :func:`tip_delta_csr` — same static
    pair-butterfly algebra, but the per-vertex reduction runs as blocked
    row sums over the vertex-major slot layout
    (:func:`pack_tip_slots`).  Exact while supports < 2²⁴ (guarded at
    pack time); parity-tested against the segment-sum path."""
    from repro.kernels import ops as kops  # local import: keep core light

    if interpret is None:
        interpret = kops.default_interpret()
    pe = jnp.concatenate([peeled_u, jnp.zeros((1,), bool)])
    vals = jnp.where(pe[slot_partner], slot_bf, 0)
    loss = kops.tip_slot_loss(vals, interpret=interpret)
    return jnp.rint(loss[:n]).astype(jnp.int32)


def pack_update_slots(w: Wedges) -> dict:
    """Slot-layout companion arrays for the Pallas support-update kernel.

    ``e1``/``e2`` map each slot to its wedge's two edge ids (sentinel m
    on padding slots, so peeled-flag gathers and loss scatters are safe
    without masking); ``valid`` marks real slots — the engine's initial
    alive matrix."""
    # the kernel carries W_p, W_p-1 and c_p as f32; past 2^24 those stop
    # being exact integers and rint() re-integerization silently corrupts
    # supports — refuse up front like every other exactness boundary
    # (W only decreases, so checking the static W0 once is sufficient)
    if w.W0.size and int(w.W0.max()) >= 2 ** 24:
        raise OverflowError(
            "pair wedge counts exceed f32 integer range (2^24); "
            "use the segment_sum path (use_pallas=False)"
        )
    packed = pack_wedge_slots(w)
    if w.n_wedges:
        idx = np.maximum(packed.idx, 0)
        e1 = np.where(packed.valid, w.wedge_e1[idx], w.m).astype(np.int32)
        e2 = np.where(packed.valid, w.wedge_e2[idx], w.m).astype(np.int32)
    else:
        e1 = np.full(packed.idx.shape, w.m, np.int32)
        e2 = e1.copy()
    return dict(
        e1=e1, e2=e2, valid=packed.valid,
        n_pairs=w.n_pairs, n_rows_pad=packed.n_rows_pad, m=w.m,
    )


# =====================================================================
# Device-side counting (segment_sum over the flat wedge list)
# =====================================================================
def _seg(x: jax.Array, ids: jax.Array, n: int) -> jax.Array:
    return jax.ops.segment_sum(x, ids, num_segments=max(n, 1))


@partial(jax.jit, static_argnames=("n_pairs",))
def _pair_counts_seg(wp: jax.Array, alive_w: jax.Array, n_pairs: int):
    return _seg(alive_w.astype(jnp.int32), wp, n_pairs)


def pair_wedge_counts(
    w: Wedges,
    alive_e: Optional[jax.Array] = None,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Alive wedge count W_p per pair.

    ``use_pallas`` routes the per-pair reduction through the blocked
    :mod:`repro.kernels.wedge_count` kernel (interpret mode on CPU).
    """
    wp = jnp.asarray(w.wedge_pair)
    if alive_e is None:
        alive_w = jnp.ones((w.n_wedges,), dtype=bool)
    else:
        alive_w = alive_e[jnp.asarray(w.wedge_e1)] & alive_e[jnp.asarray(w.wedge_e2)]
    if not use_pallas:
        return _pair_counts_seg(wp, alive_w, w.n_pairs)
    from repro.kernels import ops as kops  # local import: keep core light

    if interpret is None:
        interpret = kops.default_interpret()
    packed = pack_wedge_slots(w)
    idx = jnp.asarray(np.maximum(packed.idx, 0))
    valid = jnp.asarray(packed.valid)
    slots = jnp.where(valid, alive_w[idx], False)
    W, _ = kops.pair_wedge_counts(slots, interpret=interpret)
    return jnp.rint(W[: max(w.n_pairs, 1)]).astype(jnp.int32)


def vertex_butterflies_csr(w: Wedges, side: str = "u") -> np.ndarray:
    """⋈ per U vertex (tip support init) — exact int64, host output."""
    assert side == "u", "transpose the graph for the V side"
    bf = w.pair_butterflies0()
    out = np.zeros(w.n_u, dtype=np.int64)
    if w.n_pairs:
        np.add.at(out, w.pair_a, bf)
        np.add.at(out, w.pair_b, bf)
    return out


@partial(jax.jit, static_argnames=("n_pairs", "m"))
def _edge_butterflies_from_alive(
    alive_w: jax.Array, wp: jax.Array, we1: jax.Array, we2: jax.Array,
    n_pairs: int, m: int,
) -> jax.Array:
    W = _seg(alive_w.astype(jnp.int32), wp, n_pairs)
    contrib = jnp.where(alive_w, W[wp] - 1, 0)
    return _seg(contrib, we1, m) + _seg(contrib, we2, m)


def edge_butterflies_csr(
    w: Wedges,
    alive_e: Optional[jax.Array] = None,
    use_pallas: bool = False,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """⋈_e per edge over alive edges — the csr batch re-count.

    Each alive wedge w contributes (W_{p(w)} − 1) butterflies to both of
    its edges.  With ``use_pallas`` the W_p reduction runs in the blocked
    kernel; the scatter back to edges stays in ``segment_sum``.
    """
    if w.n_wedges == 0:
        return jnp.zeros((max(w.m, 1),), dtype=jnp.int32)[: w.m]
    we1 = jnp.asarray(w.wedge_e1)
    we2 = jnp.asarray(w.wedge_e2)
    wp = jnp.asarray(w.wedge_pair)
    if alive_e is None:
        alive_w = jnp.ones((w.n_wedges,), dtype=bool)
    else:
        alive_w = alive_e[we1] & alive_e[we2]
    if not use_pallas:
        return _edge_butterflies_from_alive(alive_w, wp, we1, we2, w.n_pairs, w.m)
    W = pair_wedge_counts(w, alive_e, use_pallas=True, interpret=interpret)
    contrib = jnp.where(alive_w, W[wp] - 1, 0)
    return _seg(contrib, we1, w.m) + _seg(contrib, we2, w.m)


def edge_butterflies0(w: Wedges) -> np.ndarray:
    """Full-graph ⋈_e — exact int64, host numpy (wing support init).

    Supports only ever decrease during peeling, so engines that verify
    this fits int32 once at init stay exact all the way down."""
    out = np.zeros(w.m, dtype=np.int64)
    if w.n_wedges:
        contrib = w.W0[w.wedge_pair] - 1
        np.add.at(out, w.wedge_e1, contrib)
        np.add.at(out, w.wedge_e2, contrib)
    return out


def total_butterflies_csr(w: Wedges) -> int:
    """⋈(G) = Σ_p C(W_p, 2) — exact int64 on host."""
    return int(w.pair_butterflies0().sum())


# =====================================================================
# Incremental peeling updates
# =====================================================================
@partial(jax.jit, static_argnames=("n",))
def tip_delta_csr(
    peeled_u: jax.Array,   # (n,) bool — U vertices peeled this round
    pair_a: jax.Array,
    pair_b: jax.Array,
    pair_bf: jax.Array,    # (n_pairs,) int32 — static C(W0, 2)
    n: int,
) -> jax.Array:
    """Δ⋈_u' = Σ_{u peeled} butterflies shared by pair (u', u).

    Pair butterfly counts are static because V is never peeled — the
    sparse analogue of the dense engine's ``pair_bf @ peel`` matvec,
    in O(n_pairs) instead of O(n²).
    """
    loss_a = jnp.where(peeled_u[pair_b], pair_bf, 0)
    loss_b = jnp.where(peeled_u[pair_a], pair_bf, 0)
    return _seg(loss_a, pair_a, n) + _seg(loss_b, pair_b, n)


def wing_loss_csr(
    peeled_e: jax.Array,   # (m,) bool — edges peeled this round
    alive_w: jax.Array,    # (n_wedges,) bool
    W: jax.Array,          # (n_pairs,) int32 — alive wedge count per pair
    we1: jax.Array,
    we2: jax.Array,
    wp: jax.Array,
    n_pairs: int,
    m: int,
):
    """Per-edge butterfly loss of one peel round (BE-Index algebra on
    pairs) — the traceable core shared by :func:`wing_update_csr` and the
    device-resident FD driver (``peel._fd_while_device``).

    A wedge dies when either of its edges is peeled.  For a surviving
    edge e:
      * e in a dying wedge w (its partner edge was peeled): e loses every
        butterfly through w — (W_old[p(w)] − 1) of them ("widow" rule);
      * e in a surviving wedge w: e loses one butterfly per dying wedge
        of the same pair — c[p(w)] of them ("survivor" rule).
    Both scatters are segment_sums; only butterflies incident to peeled
    edges are touched.

    Returns (alive_w', W', loss, n_updates).
    """
    pe1 = peeled_e[we1]
    pe2 = peeled_e[we2]
    w_dies = alive_w & (pe1 | pe2)
    c = _seg(w_dies.astype(jnp.int32), wp, n_pairs)
    surv = alive_w & ~w_dies
    surv_loss = jnp.where(surv, c[wp], 0)
    loss = (
        _seg(jnp.where(w_dies & ~pe1, W[wp] - 1, 0) + surv_loss, we1, m)
        + _seg(jnp.where(w_dies & ~pe2, W[wp] - 1, 0) + surv_loss, we2, m)
    )
    n_updates = jnp.sum((w_dies & (~pe1 | ~pe2)).astype(jnp.int32)) + jnp.sum(
        (surv & (c[wp] > 0)).astype(jnp.int32)
    )
    return alive_w & ~w_dies, W - c, loss, n_updates


def wing_update_slots(
    peeled_e: jax.Array,       # (m,) bool — edges peeled this round
    alive_slots: jax.Array,    # (n_rows_pad, K) bool — slot-layout alive
    W: jax.Array,              # (n_pairs,) int32 — alive wedges per pair
    support: jax.Array,        # (m,) int32
    slot_e1: jax.Array,        # (n_rows_pad, K) int32, sentinel m
    slot_e2: jax.Array,
    n_pairs: int,
    m: int,
    interpret: Optional[bool] = None,
):
    """Pallas-kernel variant of :func:`wing_update_csr` — same widow /
    survivor algebra, but the per-pair reduction and per-slot loss
    computation run in the blocked ``kernels.support_update`` kernel over
    the pairs-major slot layout; only the final scatter onto edges stays
    a ``segment_sum``.  Counts are re-integerized from f32 straight out
    of the kernel, so results are exact while W_p < 2²⁴ (parity-tested
    against the segment-sum path).

    Returns (alive_slots', W', support', n_updates).
    """
    from repro.kernels import ops as kops  # local import: keep core light

    if interpret is None:
        interpret = kops.default_interpret()
    rows = alive_slots.shape[0]
    W_rows = jnp.zeros((rows,), jnp.int32).at[:n_pairs].set(W)
    pe_pad = jnp.concatenate([peeled_e, jnp.zeros((1,), bool)])
    pe1 = pe_pad[slot_e1]
    pe2 = pe_pad[slot_e2]
    c1, c2, c_row = kops.support_update(
        pe1, pe2, alive_slots, W_rows, interpret=interpret
    )
    c1 = jnp.rint(c1).astype(jnp.int32)
    c2 = jnp.rint(c2).astype(jnp.int32)
    c_row = jnp.rint(c_row).astype(jnp.int32)
    loss = (
        _seg(c1.reshape(-1), slot_e1.reshape(-1), m + 1)[:m]
        + _seg(c2.reshape(-1), slot_e2.reshape(-1), m + 1)[:m]
    )
    dies = alive_slots & (pe1 | pe2)
    surv = alive_slots & ~dies
    n_updates = jnp.sum((dies & (~pe1 | ~pe2)).astype(jnp.int32)) + jnp.sum(
        (surv & (c_row[:, None] > 0)).astype(jnp.int32)
    )
    return alive_slots & ~dies, W - c_row[:n_pairs], support - loss, n_updates


@partial(jax.jit, static_argnames=("n_pairs", "m"))
def wing_update_csr(
    peeled_e: jax.Array,   # (m,) bool — edges peeled this round
    alive_w: jax.Array,    # (n_wedges,) bool
    W: jax.Array,          # (n_pairs,) int32 — alive wedge count per pair
    support: jax.Array,    # (m,) int32
    we1: jax.Array,
    we2: jax.Array,
    wp: jax.Array,
    n_pairs: int,
    m: int,
):
    """One batched incremental support update (see :func:`wing_loss_csr`)."""
    alive_w, W, loss, n_updates = wing_loss_csr(
        peeled_e, alive_w, W, we1, we2, wp, n_pairs, m
    )
    return alive_w, W, support - loss, n_updates
