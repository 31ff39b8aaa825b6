"""Fused FD round kernel — the whole peel round as ONE Pallas launch.

The FD cascade drivers (``core.peelspec._fd_while_vmapped`` /
``_fd_while_device``) used to run each round as a Pallas
``support_update`` launch plus a tail of XLA ops: the k-advance
(min-scan to the next peelable level), the frontier compaction
(θ write + alive mask update) and the loss scatter (two segment_sums).
That tail is pure dispatch overhead in the regime the vmapped driver
exists for — many small partitions, rounds bounded by latency, not
flops.  This kernel fuses the ENTIRE round body:

    live  = any(alive)                     # round accounting
    k     = max(k, min(alive ? sup : BIG)) # k-advance
    S     = alive & (sup <= k)             # peel frontier
    theta = S ? k : theta;  alive &= ~S    # frontier compaction
    ...widow/survivor support algebra...   # support update
    sup  -= scatter-add(c1, c2)            # loss applied in-kernel

so a round is one ``pallas_call`` and nothing else — the while_loop
body's jaxpr holds exactly one primitive doing real work (asserted by
``tests/test_fused_fd.py``).

Layout: grid = (B,), one program per stacked FD partition.  Each
program owns its partition's full state as VMEM-resident blocks —
``sup``/``alive``/``theta`` (1, E), the pairs-major wedge slots
(1, R, K) with sentinel edge id E (``distributed._pack_fd_slots_csr``),
per-pair alive wedge counts W (1, R) and the (1, 1) scalar carries
k/rounds/nupd.  ALL loop state flows through the kernel, so the caller
threads the outputs straight back in as the next round's inputs.

Exactness: the widow/survivor counts ride f32 lanes (same VPU shapes as
``support_update``) and are re-integerized with ``rint`` per slot, then
summed as int32 by the in-kernel scatter-add — exact while W_p < 2²⁴
(guarded at pack time; the per-edge loss itself is int32 and may exceed
2²⁴ safely).  Masks travel as int32 0/1 blocks.

The kernel runs in interpret mode only (CPU), and ``kernels/ops.py``
refuses it on a TPU backend: it cannot lower to Mosaic (see
:data:`MOSAIC_LIMITS` and docs/KERNELS.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["MOSAIC_LIMITS", "fd_round_wing_pallas", "fd_round_tip_pallas"]

_BIG = jnp.iinfo(jnp.int32).max  # == peelspec._fd_big of int32 supports

# Why Mosaic refuses this kernel (compiled for a v5e): the (1, E) /
# (1, 1) / (1, R) blocks over (B, ...) state break the (8, 128) tiling
# rule, and with B = 1 (whole-array blocks) lowering stops at the
# in-kernel gather ``S_pad[e1]`` ("Only 2D gather is supported") and the
# in-kernel scatter-add.  Lifting this needs a rewrite with neither.
MOSAIC_LIMITS = (
    "kernels.fd_round has no Mosaic (TPU) lowering — its (1, E)/(1, 1)/"
    "(1, R) blocks break the (8, 128) tiling rule and its in-kernel "
    "gather and scatter-add do not lower (\"Only 2D gather is "
    "supported\"); it runs in CPU interpret mode only")


def _advance(sup, alive, theta, k):
    """Shared k-advance + frontier compaction: returns the peel mask S
    and the updated (alive, theta, k, live) — bit-identical to the
    ``_fd_while_vmapped`` body's prologue for one partition row."""
    live = jnp.any(alive)
    k = jnp.maximum(k, jnp.min(jnp.where(alive, sup, _BIG)))
    S = alive & (sup <= k)
    theta = jnp.where(S, k, theta)
    alive = alive & ~S
    return S, alive, theta, k, live


def _fd_round_wing_kernel(sup_ref, alive_ref, theta_ref, k_ref, rounds_ref,
                          nupd_ref, aslot_ref, w_ref, e1_ref, e2_ref,
                          sup_o, alive_o, theta_o, k_o, rounds_o, nupd_o,
                          aslot_o, w_o):
    sup = sup_ref[0]                 # (E,) int32
    alive = alive_ref[0] != 0        # (E,)
    aslot = aslot_ref[0] != 0        # (R, K) wedge-slot alive
    W = w_ref[0]                     # (R,) f32 alive wedges per pair
    e1 = e1_ref[0]                   # (R, K) int32 local edge ids, sentinel E
    e2 = e2_ref[0]

    S, alive, theta, k, live = _advance(sup, alive, theta_ref[0], k_ref[0, 0])

    # widow/survivor support algebra (== kernels.ref.support_update_ref)
    S_pad = jnp.concatenate([S, jnp.zeros((1,), bool)])
    pe1 = S_pad[e1]
    pe2 = S_pad[e2]
    dies = aslot & (pe1 | pe2)
    c_row = jnp.sum(dies.astype(jnp.float32), axis=1)     # dying wedges/pair
    surv = aslot & ~dies
    wm1 = (W - 1.0)[:, None]
    surv_c = jnp.where(surv, c_row[:, None], 0.0)
    c1 = jnp.rint(jnp.where(dies & ~pe1, wm1, 0.0) + surv_c).astype(jnp.int32)
    c2 = jnp.rint(jnp.where(dies & ~pe2, wm1, 0.0) + surv_c).astype(jnp.int32)
    ci = jnp.rint(c_row).astype(jnp.int32)

    E = sup.shape[0]
    loss = (
        jnp.zeros((E + 1,), jnp.int32)   # +1: sentinel discard slot
        .at[e1.reshape(-1)].add(c1.reshape(-1))
        .at[e2.reshape(-1)].add(c2.reshape(-1))
    )[:E]
    nu = jnp.sum((dies & (~pe1 | ~pe2)).astype(jnp.int32)) + jnp.sum(
        (surv & (ci[:, None] > 0)).astype(jnp.int32)
    )

    sup_o[0] = sup - loss
    alive_o[0] = alive.astype(jnp.int32)
    theta_o[0] = theta
    k_o[0, 0] = k
    rounds_o[0, 0] = rounds_ref[0, 0] + live.astype(jnp.int32)
    nupd_o[0, 0] = nupd_ref[0, 0] + nu
    aslot_o[0] = surv.astype(jnp.int32)
    w_o[0] = W - c_row


def fd_round_wing_pallas(sup, alive, theta, k, rounds, nupd, aslot, W,
                         e1, e2, interpret: bool = True):
    """One fused wing-FD round over all B stacked partitions.

    State: sup/alive/theta (B, E) i32, k/rounds/nupd (B, 1) i32, wedge
    slots alive (B, R, K) i32, W (B, R) f32; statics e1/e2 (B, R, K)
    i32.  Returns the 8-tuple of updated state in the same order.
    """
    B, E = sup.shape
    _, R, K = e1.shape
    sE = pl.BlockSpec((1, E), lambda b: (b, 0))
    s1 = pl.BlockSpec((1, 1), lambda b: (b, 0))
    sRK = pl.BlockSpec((1, R, K), lambda b: (b, 0, 0))
    sR = pl.BlockSpec((1, R), lambda b: (b, 0))
    i32 = jnp.int32
    return pl.pallas_call(
        _fd_round_wing_kernel,
        grid=(B,),
        in_specs=[sE, sE, sE, s1, s1, s1, sRK, sR, sRK, sRK],
        out_specs=[sE, sE, sE, s1, s1, s1, sRK, sR],
        out_shape=[
            jax.ShapeDtypeStruct((B, E), i32),      # sup
            jax.ShapeDtypeStruct((B, E), i32),      # alive
            jax.ShapeDtypeStruct((B, E), i32),      # theta
            jax.ShapeDtypeStruct((B, 1), i32),      # k
            jax.ShapeDtypeStruct((B, 1), i32),      # rounds
            jax.ShapeDtypeStruct((B, 1), i32),      # nupd
            jax.ShapeDtypeStruct((B, R, K), i32),   # alive slots
            jax.ShapeDtypeStruct((B, R), jnp.float32),  # W
        ],
        interpret=interpret,
    )(sup, alive, theta, k, rounds, nupd, aslot, W, e1, e2)


def _fd_round_tip_kernel(sup_ref, alive_ref, theta_ref, k_ref, rounds_ref,
                         pa_ref, pb_ref, bf_ref,
                         sup_o, alive_o, theta_o, k_o, rounds_o):
    sup = sup_ref[0]                 # (E,) int32
    alive = alive_ref[0] != 0
    pa = pa_ref[0]                   # (L,) int32 partition-local vertex ids
    pb = pb_ref[0]
    bf = bf_ref[0]                   # (L,) int32 static pair ⋈ (0 on pad)

    S, alive, theta, k, live = _advance(sup, alive, theta_ref[0], k_ref[0, 0])

    # static pair-butterfly delta (== core.csr.tip_delta_csr): vertex u
    # loses bf(u, u') when partner u' peels; pad entries carry bf=0
    E = sup.shape[0]
    loss = (
        jnp.zeros((E,), jnp.int32)
        .at[pa].add(jnp.where(S[pb], bf, 0))
        .at[pb].add(jnp.where(S[pa], bf, 0))
    )

    sup_o[0] = sup - loss
    alive_o[0] = alive.astype(jnp.int32)
    theta_o[0] = theta
    k_o[0, 0] = k
    rounds_o[0, 0] = rounds_ref[0, 0] + live.astype(jnp.int32)


def fd_round_tip_pallas(sup, alive, theta, k, rounds, pa, pb, bf,
                        interpret: bool = True):
    """One fused tip-FD round over all B stacked partitions.

    State: sup/alive/theta (B, E) i32, k/rounds (B, 1) i32; statics
    pa/pb/bf (B, L) i32 (``pack_fd_partitions_tip_csr(stacked=True)``).
    Returns the 5-tuple of updated state in the same order.  Tip carries
    no per-wedge state (pair butterflies are static), hence no nupd.
    """
    B, E = sup.shape
    L = pa.shape[1]
    sE = pl.BlockSpec((1, E), lambda b: (b, 0))
    s1 = pl.BlockSpec((1, 1), lambda b: (b, 0))
    sL = pl.BlockSpec((1, L), lambda b: (b, 0))
    i32 = jnp.int32
    return pl.pallas_call(
        _fd_round_tip_kernel,
        grid=(B,),
        in_specs=[sE, sE, sE, s1, s1, sL, sL, sL],
        out_specs=[sE, sE, sE, s1, s1],
        out_shape=[
            jax.ShapeDtypeStruct((B, E), i32),      # sup
            jax.ShapeDtypeStruct((B, E), i32),      # alive
            jax.ShapeDtypeStruct((B, E), i32),      # theta
            jax.ShapeDtypeStruct((B, 1), i32),      # k
            jax.ShapeDtypeStruct((B, 1), i32),      # rounds
        ],
        interpret=interpret,
    )(sup, alive, theta, k, rounds, pa, pb, bf)
