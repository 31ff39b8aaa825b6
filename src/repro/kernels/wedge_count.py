"""Blocked Pallas kernel for CSR wedge counting (the csr engine's hot loop).

The csr engine reduces every butterfly quantity to per-pair alive-wedge
counts W_p.  On the flat wedge list that is a segment_sum (scatter-add);
here the same reduction is expressed over the **pairs-major padded slot
matrix** (`core.csr.PaddedCSR`): row p holds pair p's wedge-alive flags,
zero padded to a lane multiple.

The kernel tiles that matrix (bp pairs × bk slots) through VMEM and
accumulates row sums across slot blocks in a VMEM scratch accumulator —
W never round-trips to HBM between slot blocks.  On the last block it
also emits a pair butterfly **estimate** C(W, 2) in f32: exact while
W(W−1) stays inside f32's integer range (W ≲ 5790), approximate beyond —
suitable for CD range *estimation*, never for final θ (the engine's
exact path derives counts from the int32 W instead and discards this
output).  Block shapes are TPU-tile aligned (sublane 8 × lane 128 for
f32); ``interpret=True`` runs the same kernel on CPU for CI.

Per-row outputs are (n, 1) columns written in (bp, 1) blocks: Mosaic
refuses rank-1 output blocks whose XLA layout tiles differently
(``{0:T(1024)}`` vs its ``{0:T(128)}``), while a block whose last dim
equals the array's passes the (8, 128) tiling rule for any bp % 8 == 0.
The wrappers below return the flat (n,) vectors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wedge_count_pallas", "wedge_count_tile_pallas"]


def _wedge_count_kernel(slots_ref, w_ref, bf_ref, acc_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.sum(slots_ref[...], axis=1, keepdims=True)

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        w = acc_ref[...]
        w_ref[...] = w
        bf_ref[...] = w * (w - 1.0) * 0.5


def _wedge_count_tile_kernel(slots_ref, w_ref, acc_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.sum(slots_ref[...], axis=1, keepdims=True)

    @pl.when(k == pl.num_programs(1) - 1)
    def _done():
        w_ref[...] = acc_ref[...]


def wedge_count_tile_pallas(
    slots: jax.Array, bp: int = 8, bk: int = 128, interpret: bool = False
) -> jax.Array:
    """Tile-accumulate mode: exact int32 per-row partial counts.

    Used by the bounded-tile ⋈init path (``core.csr
    .tiled_butterfly_init``): each row holds a fixed-width segment of
    ONE pair's wedge flags, so a hub pair spans several rows whose
    int32 partials the host reduces in int64 — no f32 round-trip, no
    C(W, 2) emit, and therefore none of the 2²⁴ exactness ceiling of
    :func:`wedge_count_pallas`.  Per-launch device working set is one
    (bp, bk) block + the (bp, 1) accumulator regardless of tile size.

    slots: (n_rows_pad, width) int32 0/1 flags, pre-padded to (bp, bk)
    multiples.  Returns (n_rows_pad,) int32 row sums.
    """
    n, kdim = slots.shape
    assert n % bp == 0 and kdim % bk == 0, "pad slots before calling"
    grid = (n // bp, kdim // bk)
    w = pl.pallas_call(
        _wedge_count_tile_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bp, bk), lambda i, k: (i, k))],
        out_specs=pl.BlockSpec((bp, 1), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bp, 1), jnp.int32)],
        interpret=interpret,
    )(slots)
    return w[:, 0]


def wedge_count_pallas(
    slots: jax.Array, bp: int = 128, bk: int = 128, interpret: bool = False
):
    """Per-pair wedge counts + butterflies from a padded slot matrix.

    slots: (n_pairs_pad, K) f32 alive flags, pre-padded to (bp, bk)
    multiples (padding rows/slots are zero and contribute nothing).
    Returns (W, bf), both (n_pairs_pad,) f32.
    """
    n, kdim = slots.shape
    assert n % bp == 0 and kdim % bk == 0, "pad slots before calling"
    grid = (n // bp, kdim // bk)
    col = pl.BlockSpec((bp, 1), lambda i, k: (i, 0))
    w, bf = pl.pallas_call(
        _wedge_count_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bp, bk), lambda i, k: (i, k))],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bp, 1), jnp.float32)],
        interpret=interpret,
    )(slots)
    return w[:, 0], bf[:, 0]
