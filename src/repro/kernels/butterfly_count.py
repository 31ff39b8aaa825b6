"""Pallas TPU kernels for butterfly counting (DESIGN.md §2).

The paper's wedge traversal becomes MXU matmul tiles:

* ``vertex_count_kernel`` — fused: per (i, j) tile of W = A·Aᵀ compute
  C(W, 2), zero the diagonal, and row-reduce into a per-vertex
  accumulator.  W is never written to HBM (the fusion is the whole
  point: an n_u² intermediate would be memory-roofline death).
* ``matmul_kernel``       — generic tiled matmul used for the per-edge
  pass M = W·A (the −d_v correction happens in ops.py: (W−1)·A =
  W·A − Σ_k A[k, :]).

Block shapes are MXU-aligned (multiples of 128 on the matmul dims);
``ops.py`` pads inputs and picks blocks.  Validated against
``ref.py`` in interpret mode on CPU; compiled path targets TPU VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["vertex_count_pallas", "vertex_count_tile_pallas",
           "matmul_pallas"]


def _vertex_count_kernel(a_i_ref, a_j_ref, o_ref, w_ref, acc_ref, *,
                         mask_diag: bool):
    """Grid (i, j, kk): W_ij = Σ_kk A_i,kk · A_j,kkᵀ accumulates in VMEM
    over the contraction blocks, then C(W_ij, 2) row-reduces into the
    (bm, 1) per-vertex accumulator.  Blocking the contraction keeps the
    VMEM working set at (bm + bn)·bk inputs + one (bm, bn) tile however
    wide the adjacency is."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _init_rows():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kk == 0)
    def _init_tile():
        w_ref[...] = jnp.zeros_like(w_ref)

    w_ref[...] += jax.lax.dot_general(
        a_i_ref[...], a_j_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _reduce_tile():
        w = w_ref[...]
        if mask_diag:
            bm, bn = w.shape
            rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0)
            cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1)
            w = jnp.where(rows == cols, 0.0, w)
        acc_ref[...] += jnp.sum(w * (w - 1.0) * 0.5, axis=1, keepdims=True)

    @pl.when((j == pl.num_programs(1) - 1) & (kk == pl.num_programs(2) - 1))
    def _done():
        o_ref[...] = acc_ref[...]


def _vertex_count_call(A_rows, A, bm, bn, bk, mask_diag, interpret):
    rows, k = A_rows.shape
    n = A.shape[0]
    assert rows % bm == 0 and n % bn == 0 and k % bk == 0, \
        "pad rows/columns before calling"
    out = pl.pallas_call(
        functools.partial(_vertex_count_kernel, mask_diag=mask_diag),
        grid=(rows // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1), jnp.float32)],
        interpret=interpret,
    )(A_rows, A)
    return out[:, 0]


def vertex_count_pallas(
    A: jax.Array, bm: int = 128, bn: int = 128, bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Per-row-vertex butterfly counts of a padded adjacency.

    A must already be zero-padded to multiples of (bm, bn) rows and bk
    columns; padded rows are all-zero so they contribute nothing.
    """
    return _vertex_count_call(A, A, bm, bn, bk, True, interpret)


def vertex_count_tile_pallas(
    A_rows: jax.Array,
    A: jax.Array,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Tile-accumulate mode: butterfly partials for ONE row tile.

    ``A_rows`` is a (rows, k) slice of the padded adjacency ``A``; the
    host loops row tiles (``ops.vertex_butterflies_tiled``), so peak
    device compute state is one (bm, bk) × (bn, bk) block pair and one
    (bm, bn) tile no matter how many rows the graph has.  Unlike
    :func:`vertex_count_pallas` the diagonal is NOT masked in-kernel
    (the tile does not know its global row offset); the self-pair term
    is exactly C(d_r, 2) since W[r, r] = d_r, and the caller subtracts
    it on the host.
    """
    return _vertex_count_call(A_rows, A, bm, bn, bk, False, interpret)


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Tiled a @ b with VMEM accumulation (inputs pre-padded)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
