"""Public jit'd wrappers around the Pallas kernels.

Handle padding to MXU-aligned blocks, interpret-mode selection (off a
TPU backend → interpret=True; on a TPU backend → compiled Mosaic, and
interpret mode is refused there), and the bloom-major dense packing
used by ``bloom_update_pallas``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bloom_update import bloom_update_pallas
from .butterfly_count import (
    matmul_pallas,
    vertex_count_pallas,
    vertex_count_tile_pallas,
)
from .flash_attention import flash_attention_pallas
from .support_update import support_update_pallas
from .wedge_count import wedge_count_pallas, wedge_count_tile_pallas

__all__ = [
    "vertex_butterflies",
    "vertex_butterflies_tiled",
    "edge_wedge_matrix",
    "bloom_update",
    "flash_attention",
    "pack_blooms",
    "pair_wedge_counts",
    "support_update",
    "tile_row_counts",
    "tip_slot_loss",
    "default_interpret",
]


def default_interpret() -> bool:
    """Pallas interpret mode unless running on real TPU."""
    return jax.default_backend() != "tpu"


def _interpret(interpret: bool | None) -> bool:
    """Resolve a wrapper's ``interpret`` argument: ``None`` takes the
    backend default.  Interpret mode on a TPU backend is refused — it
    would emulate the kernel on the host and hide the device."""
    if interpret is None:
        return default_interpret()
    if interpret and jax.default_backend() == "tpu":
        raise ValueError(
            "Pallas interpret mode requested on a TPU backend; pass "
            "interpret=None (compiled Mosaic) instead")
    return interpret


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def vertex_butterflies(
    A: jax.Array, bm: int = 128, bn: int = 128, interpret: bool | None = None
) -> jax.Array:
    """Per-row butterfly counts via the fused count kernel."""
    interpret = _interpret(interpret)
    n = A.shape[0]
    Ap = _pad_to(_pad_to(A.astype(jnp.float32), bm, 0), 128, 1)
    # rows must also tile by bn for the column blocks of W
    Ap = _pad_to(Ap, bn, 0)
    out = vertex_count_pallas(Ap, bm=bm, bn=bn, interpret=interpret)
    return out[:n]


def _row_bucket(n: int, mult: int) -> int:
    """Round n up to a quarter-pow2 bucket (a multiple of ``mult``).

    Tile row counts vary per tile; jitting on the raw count would
    recompile the wrapper for every tile.  Bucketing to {1, 1.25, 1.5,
    1.75}·2^k caps the number of compiled shapes at O(log n) while
    wasting < 25 % rows of zero padding.
    """
    n = max(int(n), mult)
    p = 1 << (n - 1).bit_length()      # smallest pow2 >= n
    half = p // 2
    for q in (4, 5, 6, 7):
        cand = -(-(half * q // 4) // mult) * mult
        if cand >= n:
            return cand
    return -(-p // mult) * mult


@functools.partial(jax.jit, static_argnames=("bp", "bk", "interpret"))
def _tile_row_counts_inner(slots, bp, bk, interpret):
    s = _pad_to(_pad_to(slots, bp, 0), bk, 1)
    return wedge_count_tile_pallas(s, bp=bp, bk=bk, interpret=interpret)


def tile_row_counts(
    slots: np.ndarray,
    bp: int = 8,
    bk: int = 128,
    interpret: bool | None = None,
) -> np.ndarray:
    """Exact int32 row sums of an int32 0/1 slot matrix.

    The bounded-tile ⋈init path (``core.csr.tiled_butterfly_init``)
    calls this once per wedge tile; rows are fixed-width segments of a
    pair's flags, reduced to int64 totals on the host.  Row counts are
    bucketed (``_row_bucket``) so repeated tiles hit a handful of
    compiled shapes instead of one per tile.
    """
    interpret = _interpret(interpret)
    n = slots.shape[0]
    nb = _row_bucket(n, bp)
    if nb > n:
        slots = np.pad(slots, ((0, nb - n), (0, 0)))
    out = _tile_row_counts_inner(
        jnp.asarray(slots, jnp.int32), bp, bk, interpret
    )
    return np.asarray(out)[:n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def _vertex_tile_inner(A_rows, Ap, bm, bn, interpret):
    return vertex_count_tile_pallas(
        A_rows, Ap, bm=bm, bn=bn, interpret=interpret
    )


def vertex_butterflies_tiled(
    A,
    tile_rows: int = 1024,
    bm: int = 128,
    bn: int = 128,
    interpret: bool | None = None,
) -> np.ndarray:
    """Per-row butterfly counts with one row tile in flight at a time.

    Host loop over ``tile_rows``-row slices of the padded adjacency,
    each dispatched through the tile-accumulate kernel
    (``vertex_count_tile_pallas``); the kernel skips diagonal masking
    (a tile doesn't know its global row offset, and baking the offset
    in would recompile per tile), so the exact self-pair term C(d_r, 2)
    is subtracted here.  Every tile is padded to the same shape — one
    compiled program total.  Returns int64 counts.
    """
    interpret = _interpret(interpret)
    A = np.asarray(A)
    n = A.shape[0]
    deg = A.sum(axis=1).astype(np.int64)
    tile_rows = max(-(-tile_rows // bm) * bm, bm)
    Ap = np.asarray(
        _pad_to(_pad_to(jnp.asarray(A, jnp.float32), bn, 0), 128, 1)
    )
    Aj = jnp.asarray(Ap)
    out = np.zeros(n, dtype=np.float64)
    for r0 in range(0, n, tile_rows):
        r1 = min(r0 + tile_rows, n)
        tile = Ap[r0:r1]
        if tile.shape[0] < tile_rows:
            tile = np.pad(tile, ((0, tile_rows - tile.shape[0]), (0, 0)))
        part = _vertex_tile_inner(
            jnp.asarray(tile), Aj, bm, bn, interpret
        )
        out[r0:r1] = np.asarray(part, dtype=np.float64)[: r1 - r0]
    self_pair = deg * (deg - 1) // 2
    return np.rint(out).astype(np.int64) - self_pair


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def edge_wedge_matrix(
    A: jax.Array,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """M = (W − 1)·A with W = A·Aᵀ, both matmuls tiled in Pallas.

    Uses the identity (W − 1)·A = W·A − d_v so the −1 never materializes.
    Per-edge counts = M[u, v] − (d_u − 1), gathered by the caller.
    """
    interpret = _interpret(interpret)
    n, nv = A.shape
    Af = A.astype(jnp.float32)
    Ap = _pad_to(_pad_to(Af, max(bm, bn, bk), 0), bk, 1)
    W = matmul_pallas(Ap, Ap.T, bm=bm, bn=bn, bk=bk, interpret=interpret)
    Ap2 = _pad_to(_pad_to(Af, bk, 0), bn, 1)
    W = W[: Ap2.shape[0], : Ap2.shape[0]]
    M = matmul_pallas(W, Ap2, bm=bm, bn=bn, bk=bk, interpret=interpret)
    dv = jnp.sum(Af, axis=0)
    return M[:n, :nv] - dv[None, :]


@functools.partial(jax.jit, static_argnames=("bp", "bk", "interpret"))
def pair_wedge_counts(
    slots: jax.Array, bp: int = 128, bk: int = 128, interpret: bool | None = None
) -> Tuple[jax.Array, jax.Array]:
    """Per-pair wedge counts W and the f32 butterfly estimate C(W, 2)
    via the blocked wedge-count kernel (estimate is exact only while
    W(W−1) fits f32 integers — see ``wedge_count.py``).  ``slots`` is
    the pairs-major alive matrix (``core.csr.pack_wedge_slots``);
    padding is handled here."""
    interpret = _interpret(interpret)
    n = slots.shape[0]
    s = _pad_to(_pad_to(slots.astype(jnp.float32), bp, 0), bk, 1)
    W, bf = wedge_count_pallas(s, bp=bp, bk=bk, interpret=interpret)
    return W[:n], bf[:n]


@functools.partial(jax.jit, static_argnames=("bp", "bk", "interpret"))
def tip_slot_loss(
    vals: jax.Array, bp: int = 128, bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-row f32 sums of masked pair-butterfly values — the tip CD
    support delta through the blocked wedge-count kernel.

    ``vals`` is the vertex-major slot matrix (``core.csr.pack_tip_slots``)
    with each slot holding the pair's static butterfly count where the
    partner vertex was peeled this round, 0 otherwise; the kernel's
    row-sum phase IS the delta (its C(W, 2) output is ignored).  Rows
    are vertices, so the result needs no scatter.  Exact while per-row
    sums stay under 2²⁴ (guarded at pack time)."""
    interpret = _interpret(interpret)
    n = vals.shape[0]
    v = _pad_to(_pad_to(vals.astype(jnp.float32), bp, 0), bk, 1)
    W, _ = wedge_count_pallas(v, bp=bp, bk=bk, interpret=interpret)
    return W[:n]


@functools.partial(jax.jit, static_argnames=("bp", "bk", "interpret"))
def support_update(
    pe1: jax.Array,
    pe2: jax.Array,
    alive: jax.Array,
    W: jax.Array,
    bp: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One csr support-update round through the blocked Pallas kernel.

    ``pe1``/``pe2``/``alive`` are (n_rows, K) pairs-major slot flags,
    ``W`` the per-row alive wedge counts.  Rows are pairs of ONE graph
    for the CD path (``core.csr.pack_update_slots``) or the flattened
    partition×pair stack for the in-loop FD path
    (``core.peel._fd_wing_vmapped_pallas`` — partitions ride the row
    grid).  Padding to (bp, bk) tiles is handled here.  Returns
    (contrib1, contrib2, c) trimmed back to the input shape — per-slot
    losses for each slot's two edges plus dying wedges per row."""
    interpret = _interpret(interpret)
    n, kdim = pe1.shape

    def padf(x):
        return _pad_to(_pad_to(x.astype(jnp.float32), bp, 0), bk, 1)

    c1, c2, c = support_update_pallas(
        padf(pe1), padf(pe2), padf(alive),
        _pad_to(W.astype(jnp.float32), bp, 0),
        bp=bp, bk=bk, interpret=interpret,
    )
    return c1[:n, :kdim], c2[:n, :kdim], c[:n]


def pack_blooms(
    link_edge: np.ndarray,
    link_twin: np.ndarray,
    link_bloom: np.ndarray,
    nb: int,
    bb: int = 256,
) -> dict:
    """Bloom-major dense packing: row b holds bloom b's links, padded to
    the max pairs-per-bloom (rounded to a lane multiple of 128)."""
    order = np.argsort(link_bloom, kind="stable")
    le, lt, lb = link_edge[order], link_twin[order], link_bloom[order]
    counts = np.bincount(lb, minlength=nb)
    K = max(int(counts.max() if counts.size else 1), 1)
    K = int(-(-K // 128) * 128)
    nb_pad = int(-(-max(nb, 1) // bb) * bb)
    col = np.zeros(le.size, dtype=np.int64)
    off = np.zeros(nb + 1, dtype=np.int64)
    np.cumsum(counts, out=off[1:])
    col = np.arange(le.size) - off[lb]
    dense = dict(
        le=np.full((nb_pad, K), -1, np.int32),
        lt=np.full((nb_pad, K), -1, np.int32),
        valid=np.zeros((nb_pad, K), bool),
        canon=np.zeros((nb_pad, K), bool),
    )
    dense["le"][lb, col] = le
    dense["lt"][lb, col] = lt
    dense["valid"][lb, col] = True
    dense["canon"][lb, col] = le < lt
    dense["nb"] = nb
    dense["nb_pad"] = nb_pad
    dense["K"] = K
    return dense


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def bloom_update(
    peeled: jax.Array,       # (m+1,) bool, sentinel last
    alive_pair: jax.Array,   # [nb_pad, K] bool
    k_alive: jax.Array,      # [nb_pad] f32
    le: jax.Array,           # [nb_pad, K] int32 (−1 → sentinel)
    lt: jax.Array,
    canon: jax.Array,        # [nb_pad, K] bool
    m: int = 0,
    bb: int = 256,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One batched support-update round through the Pallas kernel.

    Returns (loss per edge (m,), c per bloom, new alive_pair)."""
    interpret = _interpret(interpret)
    sent = peeled.shape[0] - 1
    lei = jnp.where(le < 0, sent, le)
    lti = jnp.where(lt < 0, sent, lt)
    pe = peeled[lei]
    pt = peeled[lti]
    contrib, c = bloom_update_pallas(
        pe, pt, alive_pair, canon, k_alive, bb=bb, interpret=interpret
    )
    pair_dies = alive_pair & (pe | pt)
    loss = jax.ops.segment_sum(
        contrib.reshape(-1), lei.reshape(-1), num_segments=sent + 1
    )[:-1]
    return loss, c, alive_pair & ~pair_dies


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, H, Sk, D]
    v: jax.Array,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = _interpret(interpret)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(bq, sq) if sq % min(bq, sq) == 0 else bq
    qr = _pad_to(q.reshape(b * h, sq, d), bq, 1)
    kr = _pad_to(k.reshape(b * h, sk, d), bk, 1)
    vr = _pad_to(v.reshape(b * h, sk, d), bk, 1)
    # padded keys must never win the softmax: mask via an explicit -inf
    # key would complicate the kernel; instead rely on causal masking for
    # the padded tail (padded queries are discarded, padded keys have
    # k_ids > every real q_id when causal).  For non-causal, require
    # exact multiples.
    if not causal:
        assert sq % bq == 0 and sk % bk == 0
    # the causal diagonal offset must come from the LOGICAL sq/sk, not the
    # padded shapes — padded key ids then sit above every real query id
    # and mask themselves out
    out = flash_attention_pallas(
        qr, kr, vr, causal=causal, bq=bq, bk=bk, offset=sk - sq,
        interpret=interpret
    )
    return out[:, :sq].reshape(b, h, sq, d)
