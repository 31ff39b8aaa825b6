"""Blocked Pallas kernel for the csr engine's batched support update.

``core.csr.wing_loss_csr`` is a segment-subtract over the flat wedge
list: every peeled edge kills its wedges, and each death charges
butterfly losses to the surviving edges (widow / survivor algebra).
Here the same round runs over the **pairs-major padded slot matrix**
(`core.csr.PaddedCSR`): row p holds pair p's wedges, so the dying-wedge
count c_p is a row reduction and every per-slot contribution depends
only on its own flags plus (c_p, W_p).

The kernel tiles (bp pairs × bk slots) through VMEM with a two-phase
grid per row block:

  phase 0 — accumulate c_p (dying wedges per pair) across slot blocks in
            a VMEM scratch; nothing is written to HBM;
  phase 1 — re-stream the same slot blocks and emit the per-slot losses
            ``contrib1`` (to edge e1) and ``contrib2`` (to edge e2),
            plus c on the last block.

Per slot w of pair p (alive, flags pe1/pe2 = "edge i peeled"):

    dies          = alive ∧ (pe1 ∨ pe2)
    contrib1[w]   = dies ∧ ¬pe1 ? W_p − 1 : (alive ∧ ¬dies ? c_p : 0)
    contrib2[w]   = dies ∧ ¬pe2 ? W_p − 1 : (alive ∧ ¬dies ? c_p : 0)

The caller scatters contribs onto edges with one ``segment_sum`` per
side (``kernels.ops.support_update`` / ``core.csr.wing_update_slots``).
Counts travel as f32 through the MXU-aligned tiles — exact while W_p
fits f32 integers (< 2²⁴); the flat ``segment_sum`` path stays the
engine's exactness reference.  ``interpret=True`` runs the same kernel
on CPU for CI parity tests; compiled on TPU.

Two consumers drive the kernel:

  * **CD rounds** — ``core.csr.wing_update_slots`` over one graph-wide
    slot matrix (``wing_decomposition(use_pallas=True)``);
  * **the FD while_loop body** — ``core.peel._fd_wing_vmapped_pallas``
    flattens the stacked per-partition slot blocks along rows into one
    (B·R, K) matrix, so a single launch per peel round covers every
    partition of the single-dispatch Phase 2.  The row grid is
    oblivious to the partition structure: c_p stays a pure row
    reduction either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["support_update_pallas"]


def _support_update_kernel(
    pe1_ref, pe2_ref, alive_ref, w_ref,
    c1_ref, c2_ref, c_ref, acc_ref,
):
    phase = pl.program_id(1)
    k = pl.program_id(2)

    alive = alive_ref[...]
    dies = alive * jnp.maximum(pe1_ref[...], pe2_ref[...])

    @pl.when((phase == 0) & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(phase == 0)
    def _accumulate():
        acc_ref[...] += jnp.sum(dies, axis=1, keepdims=True)

    @pl.when(phase == 1)
    def _emit():
        c = acc_ref[...]                                 # (bp, 1)
        surv_loss = (alive - dies) * c                   # survivor rule
        widow = dies * (w_ref[...] - 1.0)                # widow rule
        c1_ref[...] = (1.0 - pe1_ref[...]) * widow + surv_loss
        c2_ref[...] = (1.0 - pe2_ref[...]) * widow + surv_loss
        c_ref[...] = c


def support_update_pallas(
    pe1: jax.Array,
    pe2: jax.Array,
    alive: jax.Array,
    W: jax.Array,
    bp: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """One support-update round over pairs-major slot matrices.

    pe1/pe2/alive: (n_pairs_pad, K) f32 flags, pre-padded to (bp, bk)
    multiples (padding slots have alive=0 and contribute nothing).
    W: (n_pairs_pad,) f32 current alive wedge count per pair.
    Returns (contrib1, contrib2, c): per-slot losses for each edge side
    and the dying-wedge count per pair.
    """
    n, kdim = pe1.shape
    assert n % bp == 0 and kdim % bk == 0, "pad slots before calling"
    grid = (n // bp, 2, kdim // bk)
    slot_spec = pl.BlockSpec((bp, bk), lambda i, ph, k: (i, k))
    # per-row vectors travel as (n, 1) columns: Mosaic refuses rank-1
    # (bp,) blocks (see kernels.wedge_count)
    row_spec = pl.BlockSpec((bp, 1), lambda i, ph, k: (i, 0))
    c1, c2, c = pl.pallas_call(
        _support_update_kernel,
        grid=grid,
        in_specs=[slot_spec, slot_spec, slot_spec, row_spec],
        out_specs=[slot_spec, slot_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, kdim), jnp.float32),
            jax.ShapeDtypeStruct((n, kdim), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bp, 1), jnp.float32)],
        interpret=interpret,
    )(pe1, pe2, alive, W.reshape(n, 1))
    return c1, c2, c[:, 0]
