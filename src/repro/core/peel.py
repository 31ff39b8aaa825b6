"""PBNG two-phased peeling (§3) — tip and wing decomposition in JAX.

Phase 1 — **coarse-grained decomposition (CD)**: iteratively peel every
entity whose support lies in the current range [θ(i), θ(i+1)).  Each round
is one fully-parallel masked update (the only global synchronization
point), a dramatic reduction versus level-by-level peeling.

Phase 2 — **fine-grained decomposition (FD)**: partitions are mutually
independent given the support-initialization vector ⋈init, so each is
peeled to exact entity numbers with *zero* communication.  Partitions are
processed in LPT (longest-processing-time) order.

Both phases are driven by the entity-agnostic core in ``core.peelspec``
— :func:`tip_decomposition` and :func:`wing_decomposition` only build
the :class:`~repro.core.peelspec.PeelSpec` (supports, workload proxy,
incremental update rule, FD packers) for their entity universe and hand
it to ``peelspec.decompose``.  The CD round loop, range selection and
all three FD cascade drivers exist exactly once, shared by every engine
below and by ``core.distributed``.

Three engines:
  * ``engine="dense"``   — TPU-native: supports re-counted per round with
    masked MXU matmuls (the paper's §5.1 batch re-count optimization taken
    to its logical extreme on TPU).  O(n²) memory — guarded by
    ``REPRO_DENSE_MAX_ELEMS``.
  * ``engine="beindex"`` — paper-faithful: BE-Index twin/bloom bookkeeping
    with ``segment_sum`` replacing atomics (alg.4/alg.6 semantics).
  * ``engine="csr"``     — sparse: ParButterfly-style wedge-list counting
    with incremental ``segment_sum`` updates (``core.csr``).  O(Σ deg²)
    memory — the only engine that scales past dense adjacency.

All return identical θ (validated against the pure-python BUP oracle).
"""
from __future__ import annotations

import os
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import counting, csr
from .. import obs
from .beindex import BEIndex, build_beindex
from .graph import BipartiteGraph
from .peelspec import (  # noqa: F401 — canonical home is peelspec; kept
    PeelResult,           # importable from here for compatibility
    PeelSpec,
    PeelStats,
    AdaptiveTarget as _AdaptiveTarget,
    _bucket_pad,
    _fd_cascade,
    _fd_while_device,
    _fd_while_vmapped,
    _find_range,
    _lpt_order,
    _pad_zeros,
)
from . import peelspec

__all__ = [
    "PeelStats",
    "PeelResult",
    "PeelSpec",
    "build_peel_spec",
    "tip_decomposition",
    "wing_decomposition",
    "wing_decomposition_bepc",
    "bup_levels",
]


def build_peel_spec(
    g: BipartiteGraph,
    kind: str,
    stats: PeelStats,
    side: str = "u",
    engine: str = "csr",
    batch_recount="adaptive",
    be: Optional[BEIndex] = None,
    fd_driver: str = "device",
    use_pallas: bool = False,
    sup0: Optional[np.ndarray] = None,
    wed: Optional["csr.Wedges"] = None,
) -> PeelSpec:
    """Build the :class:`PeelSpec` for a ``(kind, engine)`` universe.

    The shared front door for :func:`tip_decomposition`,
    :func:`wing_decomposition` and the streaming updater
    (``repro.streaming``): one place validates the engine/driver matrix
    and hands back the spec without running the decomposition, so a
    caller that already knows the support vector can drive
    ``peelspec.cd_loop`` / ``peelspec.run_fd`` directly.

    ``sup0`` injects a precomputed ⋈init vector (int64, one entry per
    entity of ``kind``) — honored by both csr specs and the wing dense
    spec, where it skips the from-scratch butterfly count (the streaming
    path maintains it incrementally via wedge-local deltas).  The tip
    dense spec recounts regardless: its device CD state needs the
    counting pass anyway.  ``wed`` likewise injects prebuilt wedge
    structures for the csr specs.  Injection never changes results —
    only who pays for the count."""
    if kind not in ("tip", "wing"):
        raise ValueError(kind)
    if kind == "tip":
        if engine not in ("dense", "csr"):
            raise ValueError(engine)
    else:
        if engine not in ("beindex", "dense", "csr"):
            raise ValueError(engine)
    if fd_driver not in ("device", "host", "vmapped"):
        raise ValueError(fd_driver)
    if kind == "tip" and use_pallas and engine != "csr":
        raise ValueError("use_pallas applies to engine='csr' only")
    if kind == "tip":
        gg = g if side == "u" else g.transpose()
        if engine == "csr":
            return _tip_spec_csr(gg, stats, use_pallas=use_pallas,
                                 sup0=sup0, wed=wed)
        return _tip_spec_dense(gg, batch_recount, stats)
    if engine == "beindex":
        return _wing_spec_beindex(g, be, stats)
    if engine == "csr":
        return _wing_spec_csr(g, stats, use_pallas=use_pallas,
                              sup0=sup0, wed=wed)
    return _wing_spec_dense(g, stats, sup0=sup0)


# =====================================================================
# Entity-specific single-dispatch (vmapped) FD bodies
# =====================================================================
# Each body's ``update`` rule lives in a ``*_update`` builder shared by
# the default entry and its ``*_rings`` telemetry twin (a separate jit
# entry with a static ``ring_cap``), so the peeling algebra exists once
# while the default entry's jaxpr stays byte-identical to the
# pre-instrumentation tree (tests/goldens/obs_jaxprs.json).

def _tip_vmapped_update(pag, pbg, bff, B, Emax):
    def update(S, aux):
        Sf = S.reshape(-1)
        loss = (
            jax.ops.segment_sum(
                jnp.where(Sf[pbg], bff, 0), pag, num_segments=B * Emax)
            + jax.ops.segment_sum(
                jnp.where(Sf[pag], bff, 0), pbg, num_segments=B * Emax)
        ).reshape(B, Emax)
        return loss, aux, jnp.int32(0)

    return update


@jax.jit
def _fd_tip_vmapped(
    pag: jax.Array,      # (W,) int32 — globalized pair endpoints b·Emax+u
    pbg: jax.Array,
    bff: jax.Array,      # (W,) int32 — static pair butterflies (0 on pad)
    mine: jax.Array,     # (B, E) bool — partition members
    sup0: jax.Array,     # (B, E) int32 — ⋈init (zero outside mine)
):
    """All tip-FD partitions in a single while_loop (one dispatch).

    :func:`csr.tip_delta_csr` over the ragged-concatenated pair lists
    with the partition axis folded into pre-globalized segment ids
    (partition b's vertex u → segment b·Emax+u): one flat
    ``segment_sum`` pass per round covers every partition.  Padding
    pairs carry bf=0 and are algebra-neutral."""
    B, Emax = mine.shape
    update = _tip_vmapped_update(pag, pbg, bff, B, Emax)
    return _fd_while_vmapped(mine, sup0, update, jnp.int32(0))


@partial(jax.jit, static_argnames=("ring_cap",))
def _fd_tip_vmapped_rings(pag, pbg, bff, mine, sup0, ring_cap: int):
    """:func:`_fd_tip_vmapped` + per-round counter rings (obs)."""
    B, Emax = mine.shape
    update = _tip_vmapped_update(pag, pbg, bff, B, Emax)
    return peelspec._fd_while_vmapped_rings(
        mine, sup0, update, jnp.int32(0), ring_cap)


def _wing_vmapped_update(e1g, e2g, wpg, B, Emax, n_pairs):
    def update(S, aux):
        alive_w, W = aux                      # (W,), (n_pairs,)
        S_pad = jnp.concatenate(
            [S, jnp.zeros((B, 1), bool)], axis=1).reshape(-1)
        pe1 = S_pad[e1g]
        pe2 = S_pad[e2g]
        w_dies = alive_w & (pe1 | pe2)
        c = jax.ops.segment_sum(
            w_dies.astype(jnp.int32), wpg, num_segments=n_pairs)
        surv = alive_w & ~w_dies
        surv_loss = jnp.where(surv, c[wpg], 0)
        nseg = B * (Emax + 1)
        loss = (
            jax.ops.segment_sum(
                jnp.where(w_dies & ~pe1, W[wpg] - 1, 0) + surv_loss,
                e1g, num_segments=nseg)
            + jax.ops.segment_sum(
                jnp.where(w_dies & ~pe2, W[wpg] - 1, 0) + surv_loss,
                e2g, num_segments=nseg)
        ).reshape(B, Emax + 1)[:, :Emax]
        nu = jnp.sum((w_dies & (~pe1 | ~pe2)).astype(jnp.int32)) + jnp.sum(
            (surv & (c[wpg] > 0)).astype(jnp.int32)
        )
        return loss, (alive_w & ~w_dies, W - c), nu

    return update


@partial(jax.jit, static_argnames=("n_pairs",))
def _fd_wing_vmapped(
    e1g: jax.Array,      # (W,) int32 — globalized edge ids b·(Emax+1)+e
    e2g: jax.Array,
    wpg: jax.Array,      # (W,) int32 — globalized pair ids (dead pad → n_pairs-ish slot)
    alive0: jax.Array,   # (W,) bool — wedges touching their partition
    W0: jax.Array,       # (n_pairs,) int32 — alive ≥i wedges per pair
    mine: jax.Array,     # (B, E) bool
    sup0: jax.Array,     # (B, E) int32
    n_pairs: int,
):
    """All wing-FD partitions in a single while_loop (one dispatch).

    The per-round update is :func:`csr.wing_loss_csr`'s widow/survivor
    algebra over the ragged-CONCATENATED wedge lists: the partition axis
    is folded into pre-globalized segment ids (partition b's edge e →
    segment b·(Emax+1)+e), so every round is ONE flat ``segment_sum``
    pass whose work is Σ|touching wedges| with zero stacking padding —
    and one scatter-add instead of a batched one.  No collectives
    anywhere."""
    B, Emax = mine.shape
    update = _wing_vmapped_update(e1g, e2g, wpg, B, Emax, n_pairs)
    return _fd_while_vmapped(mine, sup0, update, (alive0, W0))


@partial(jax.jit, static_argnames=("n_pairs", "ring_cap"))
def _fd_wing_vmapped_rings(e1g, e2g, wpg, alive0, W0, mine, sup0,
                           n_pairs: int, ring_cap: int):
    """:func:`_fd_wing_vmapped` + per-round counter rings (obs)."""
    B, Emax = mine.shape
    update = _wing_vmapped_update(e1g, e2g, wpg, B, Emax, n_pairs)
    return peelspec._fd_while_vmapped_rings(
        mine, sup0, update, (alive0, W0), ring_cap)


def _wing_pallas_update(slot_e1, slot_e2, B, Emax, interpret):
    from repro.kernels import ops as kops  # local import: keep core light

    _, R, K = slot_e1.shape
    # globalize slot edge ids: partition b's edge e → b·(Emax+1) + e
    # (sentinel Emax lands in b's own discard slot)
    off = (jnp.arange(B, dtype=jnp.int32) * (Emax + 1))[:, None, None]
    e1g = (slot_e1 + off).reshape(B * R, K)
    e2g = (slot_e2 + off).reshape(B * R, K)

    def update(S, aux):
        alive_slots, W = aux                       # (B·R, K), (B·R)
        S_pad = jnp.concatenate(
            [S, jnp.zeros((B, 1), bool)], axis=1).reshape(-1)
        pe1 = S_pad[e1g]
        pe2 = S_pad[e2g]
        c1, c2, c_row = kops.support_update(
            pe1, pe2, alive_slots, W, interpret=interpret
        )
        c1 = jnp.rint(c1).astype(jnp.int32)
        c2 = jnp.rint(c2).astype(jnp.int32)
        c_row = jnp.rint(c_row).astype(jnp.int32)
        nseg = B * (Emax + 1)
        loss = (
            jax.ops.segment_sum(c1.reshape(-1), e1g.reshape(-1),
                                num_segments=nseg)
            + jax.ops.segment_sum(c2.reshape(-1), e2g.reshape(-1),
                                  num_segments=nseg)
        ).reshape(B, Emax + 1)[:, :Emax]
        dies = alive_slots & (pe1 | pe2)
        surv = alive_slots & ~dies
        nu = jnp.sum((dies & (~pe1 | ~pe2)).astype(jnp.int32)) + jnp.sum(
            (surv & (c_row[:, None] > 0)).astype(jnp.int32)
        )
        return loss, (alive_slots & ~dies, W - c_row), nu

    return update


@partial(jax.jit, static_argnames=("interpret",))
def _fd_wing_vmapped_pallas(
    slot_e1: jax.Array,     # (B, R, K) int32 — local edge ids, sentinel E
    slot_e2: jax.Array,
    valid0: jax.Array,      # (B, R, K) bool — initial alive slots
    W0: jax.Array,          # (B, R) int32 — alive wedges per slot row
    mine: jax.Array,        # (B, E) bool
    sup0: jax.Array,        # (B, E) int32
    interpret: bool = True,
):
    """Single-dispatch wing FD with the blocked Pallas ``support_update``
    kernel INSIDE the while_loop body.

    The stacked pairs-major slot blocks flatten along rows into one
    (B·R, K) matrix, so each round is ONE kernel launch covering every
    partition (the partition axis rides the kernel's row grid — no vmap
    over ``pallas_call`` needed); only the loss scatter back onto the
    per-partition edge slots stays a ``segment_sum``.  Counts are
    re-integerized from f32 straight out of the kernel — exact while
    W_p < 2²⁴ (guarded at pack time), parity-tested against the
    segment-sum body.
    """
    B, Emax = mine.shape
    _, R, K = slot_e1.shape
    update = _wing_pallas_update(slot_e1, slot_e2, B, Emax, interpret)
    return _fd_while_vmapped(
        mine, sup0, update, (valid0.reshape(B * R, K), W0.reshape(B * R))
    )


@partial(jax.jit, static_argnames=("interpret", "ring_cap"))
def _fd_wing_vmapped_pallas_rings(slot_e1, slot_e2, valid0, W0, mine, sup0,
                                  interpret: bool, ring_cap: int):
    """:func:`_fd_wing_vmapped_pallas` + per-round counter rings (obs)."""
    B, Emax = mine.shape
    _, R, K = slot_e1.shape
    update = _wing_pallas_update(slot_e1, slot_e2, B, Emax, interpret)
    return peelspec._fd_while_vmapped_rings(
        mine, sup0, update,
        (valid0.reshape(B * R, K), W0.reshape(B * R)), ring_cap)


# =====================================================================
# Entity-specific per-partition (device) FD bodies
# =====================================================================
def _tip_device_update(pa, pb, pbf, n):
    def update(S, aux):
        loss = csr.tip_delta_csr(S, pa, pb, pbf, n)
        return loss, aux, jnp.int32(0)

    return update


@partial(jax.jit, static_argnames=("n",))
def _fd_tip_device(
    mine: jax.Array,      # (n,) bool — partition members
    sup0: jax.Array,      # (n,) int32 — ⋈init (zero outside mine)
    pa: jax.Array,        # partition-local pair endpoints (global ids)
    pb: jax.Array,
    pbf: jax.Array,       # (n_pairs_i,) int32 static pair butterflies
    n: int,
):
    """Whole tip-FD cascade of one partition in a single while_loop."""
    update = _tip_device_update(pa, pb, pbf, n)
    return _fd_while_device(mine, sup0, update, jnp.int32(0))


@partial(jax.jit, static_argnames=("n", "ring_cap"))
def _fd_tip_device_rings(mine, sup0, pa, pb, pbf, n: int, ring_cap: int):
    """:func:`_fd_tip_device` + per-round counter rings (obs)."""
    update = _tip_device_update(pa, pb, pbf, n)
    return peelspec._fd_while_device_rings(
        mine, sup0, update, jnp.int32(0), ring_cap)


def _wing_device_update(we1, we2, wp, n_pairs, m):
    def update(S, aux):
        alive_w, W = aux
        alive_w, W, loss, nu = csr.wing_loss_csr(
            S, alive_w, W, we1, we2, wp, n_pairs, m
        )
        return loss, (alive_w, W), nu

    return update


@partial(jax.jit, static_argnames=("n_pairs", "m"))
def _fd_wing_device(
    mine: jax.Array,      # (m,) bool — partition members
    sup0: jax.Array,      # (m,) int32 — ⋈init (zero outside mine)
    alive_w0: jax.Array,  # (n_kept,) bool — wedges of the ≥i subgraph
    W0: jax.Array,        # (n_pairs,) int32 — alive wedge count per pair
    we1: jax.Array,
    we2: jax.Array,
    wp: jax.Array,
    n_pairs: int,
    m: int,
):
    """Whole wing-FD cascade of one partition in a single while_loop."""
    update = _wing_device_update(we1, we2, wp, n_pairs, m)
    return _fd_while_device(mine, sup0, update, (alive_w0, W0))


@partial(jax.jit, static_argnames=("n_pairs", "m", "ring_cap"))
def _fd_wing_device_rings(mine, sup0, alive_w0, W0, we1, we2, wp,
                          n_pairs: int, m: int, ring_cap: int):
    """:func:`_fd_wing_device` + per-round counter rings (obs)."""
    update = _wing_device_update(we1, we2, wp, n_pairs, m)
    return peelspec._fd_while_device_rings(
        mine, sup0, update, (alive_w0, W0), ring_cap)


# Smallest wedge-list size the compacting wing FD driver shrinks to.
# Below it a relaunch (a scalar readback, a compaction and a fresh loop)
# costs more than the dead wedge slots it stops re-reading.
_FD_COMPACT_FLOOR = 4096


def _wedge_shrink_limit(size: int) -> int:
    """Most live wedges at which a wedge list of ``size`` slots moves to
    a smaller power-of-two size: the largest power of two below
    ``size``, or -1 where ``size`` is at the floor and never shrinks."""
    if size <= _FD_COMPACT_FLOOR:
        return -1
    return 1 << ((size - 1).bit_length() - 1)


def _wedge_shrink_size(live: int) -> int:
    """The power-of-two size ``live`` wedges move to (at least the
    floor)."""
    return max(1 << max(live - 1, 0).bit_length(), _FD_COMPACT_FLOOR)


@partial(jax.jit, static_argnames=("n_pairs", "m"))
def _fd_wing_chunk(state, we1, we2, wp, n_pairs: int, m: int):
    """Rounds of :func:`_fd_wing_device` on its loop carry ``state``
    until the partition drains or its live wedges fit a smaller size
    (:func:`_wedge_shrink_limit` of the wedge arrays' length).

    Returns ``(state', left)``: ``left`` is the live-wedge count, or -1
    once the partition has drained."""
    limit = _wedge_shrink_limit(we1.shape[0])
    update = _wing_device_update(we1, we2, wp, n_pairs, m)

    def n_live(st):
        alive_w, _ = st[2]
        return jnp.sum(alive_w.astype(jnp.int32))

    def cond(carry):
        st, live = carry
        return jnp.any(st[0]) & (live > limit)

    def body(carry):
        st = peelspec._fd_round(carry[0], update)
        return st, n_live(st)

    state, live = jax.lax.while_loop(cond, body, (state, n_live(state)))
    return state, jnp.where(jnp.any(state[0]), live, -1)


@partial(jax.jit, static_argnames=("size",))
def _compact_wedges(alive_w, we1, we2, wp, size: int):
    """The live wedges packed in order to the front of ``size`` slots;
    the slots after them are dead zero wedges, which are inert."""
    dest = jnp.where(alive_w, jnp.cumsum(alive_w, dtype=jnp.int32) - 1, size)

    def pack(x):
        return jnp.zeros((size,), x.dtype).at[dest].set(x, mode="drop")

    return pack(alive_w), pack(we1), pack(we2), pack(wp)


def _fd_wing_compacting(mine, sup0, alive_w0, W0, we1, we2, wp,
                        n_pairs: int, m: int, part: int = 0):
    """:func:`_fd_wing_device`'s cascade (same arguments and results,
    bit-identical) with the wedge list shrunk as its wedges die.

    Each launch of :func:`_fd_wing_chunk` runs until the partition
    drains or its live wedges fit a smaller power-of-two size; the host
    reads that count, packs the live wedges into arrays of the smaller
    size (:func:`_compact_wedges`) and relaunches there, the edge-sized
    state staying on the device.  A dead wedge adds nothing to any loss
    or pair count, so dropping it changes no result; later rounds just
    stop re-reading it.  Each compaction plus relaunch is an
    ``fd.compact`` span (args ``part``, ``live``, ``size_from``,
    ``size_to``); ``part`` only labels those spans."""
    zero = jnp.int32(0)
    state = (mine, sup0, (alive_w0, W0), jnp.zeros_like(sup0), zero, zero,
             zero)
    state, left = _fd_wing_chunk(state, we1, we2, wp, n_pairs=n_pairs, m=m)
    size = we1.shape[0]
    while _wedge_shrink_limit(size) >= 0:
        live = int(left)
        if live < 0:
            break
        new = _wedge_shrink_size(live)
        with obs.span("fd.compact", cat="fd.compact", part=int(part),
                      live=live, size_from=size, size_to=new):
            alive, sup, (alive_w, W), *rest = state
            alive_w, we1, we2, wp = _compact_wedges(
                alive_w, we1, we2, wp, size=new)
            state, left = _fd_wing_chunk(
                (alive, sup, (alive_w, W), *rest), we1, we2, wp,
                n_pairs=n_pairs, m=m)
        size = new
    _, _, _, theta, _, rounds, nupd = state
    return theta, rounds, nupd


def _drain_rings(mode, parts, rounds, rings, cap):
    """Hand one FD launch's counter rings to the active timeline
    collector (no-op when the obs layer is off)."""
    col = obs.active_collector()
    if col is not None:
        col.record_fd_rings(mode, parts, rounds,
                            [np.asarray(r) for r in rings], cap)


def _dense_guard(n_u: int, n_v: int) -> None:
    """Refuse dense-engine allocations that cannot fit.

    The dense engine materializes an n_u×n_v adjacency and an n_u×n_u
    wedge matrix; past ``REPRO_DENSE_MAX_ELEMS`` elements (default 2²⁸ ≈
    1 GiB of f32) that is memory-roofline death, so fail fast with a
    pointer at the csr engine instead of letting XLA OOM.
    """
    limit = int(os.environ.get("REPRO_DENSE_MAX_ELEMS", str(2 ** 28)))
    need = max(n_u * n_v, n_u * n_u)
    if need > limit:
        raise MemoryError(
            f"dense engine needs a {n_u}x{max(n_v, n_u)} matrix "
            f"({need} > REPRO_DENSE_MAX_ELEMS={limit}); "
            "use engine='csr' for graphs this large"
        )


# =====================================================================
# Tip decomposition (vertex peeling)
# =====================================================================
@partial(jax.jit, static_argnames=())
def _tip_recount(A: jax.Array, alive: jax.Array) -> jax.Array:
    return counting.vertex_butterflies(A * alive[:, None].astype(A.dtype))


@jax.jit
def _tip_fd_delta(pair_bf: jax.Array, peel: jax.Array) -> jax.Array:
    """Δ⋈_u' = Σ_{u peeled} (butterflies shared by pair (u', u))."""
    return pair_bf @ peel.astype(pair_bf.dtype)


def tip_decomposition(
    g: BipartiteGraph,
    side: str = "u",
    P: int = 16,
    batch_recount="adaptive",
    engine: str = "dense",
    fd_driver: str = "device",
    use_pallas: bool = False,
    sup0: Optional[np.ndarray] = None,
) -> PeelResult:
    """PBNG tip decomposition (§3.2) — θ per U (or V) vertex.

    ``engine``/``fd_driver`` matrix (all combinations θ-bit-identical):

    ========  =====================================  ====================
    engine    support counting / update              fd_driver
    ========  =====================================  ====================
    dense     masked MXU matmul re-counts, O(n²)     (host cascade)
    csr       incremental pair updates on the pair   device │ vmapped │ host
              list (MXU product or wedge list)
    ========  =====================================  ====================

    Example::

        from repro.core import random_bipartite, tip_decomposition
        g = random_bipartite(1000, 800, 8000, seed=0)
        res = tip_decomposition(g, side="u", engine="csr", P=8)
        print(res.theta.max(), res.stats.rho_cd)

    ``engine="dense"`` (default) re-counts with masked MXU matmuls;
    ``engine="csr"`` peels on the U pair list (``core.csr``) with purely
    incremental pair updates, the only option once the n×n wedge matrix
    stops fitting.  The csr engine chooses from the graph, with no flag:

    * **the pair source** (``csr.pair_source``, from n = |peeled side|,
      the other side's size k and W = Σ C(d, 2) over it): one int8
      adjacency product on the MXU (``csr.tip_pair_counts``, exact int32
      accumulation) where its operand and result fit 512 MiB and
      256 MiB and its cost, 2 ms + n²·(8 ns + k·0.0073 ns), is at most
      the host wedge list's W·100 ns (host rates timed on an x86 host,
      the multiply-add the v5e compiler's estimate); the host wedge
      list otherwise;
    * **the count width** (``csr.count_bits``, from the largest ⋈init
      and pair count): int32, or int64 for supports, deltas, the FD
      loop's state and θ, under JAX's scoped 64-bit mode
      (``csr.count_scope``) so the int32 programs stay as they are.

    ``stats`` reports both (``pair_source``, ``pair_rows``, ``pair_k``,
    ``pair_dtype``, ``count_bits``), and the result carries the pair list
    (``PeelResult.pairs``) for ``build_hierarchy``.

    ``fd_driver`` (csr engine only): ``"device"`` (default) peels each FD
    partition in a single ``lax.while_loop`` dispatch — zero host↔device
    transfers inside a partition; ``"vmapped"`` stacks ALL partitions
    into one shape-bucketed layout and runs the whole Phase 2 as ONE
    batched while_loop (a single dispatch total); ``"host"`` drives
    rounds from a python loop (the PR-1 baseline kept for A/B
    benchmarks).

    ``use_pallas`` (csr engine only): run CD support updates through the
    blocked ``kernels.wedge_count`` row-sum kernel on the vertex-major
    pair-slot layout (``csr.tip_delta_slots``; interpret mode off-TPU)
    instead of flat segment_sums — θ and round/update counts
    parity-locked either way.

    ``batch_recount`` (dense engine only): the §5.1 batch optimization
    knob —
      * ``"adaptive"`` (default, paper-faithful): per round, re-count all
        survivors iff the frontier's wedge workload exceeds the counting
        bound ∧cnt = Σ_e min(d_u, d_v); otherwise apply incremental
        pairwise updates.
      * ``True`` — always re-count; ``False`` — always incremental
        (the PBNG-- ablation).
    """
    stats = PeelStats(
        engine=engine,
        fd_driver=fd_driver if engine == "csr" else "host",
        side=side,
    )
    spec = build_peel_spec(
        g, "tip", stats, side=side, engine=engine,
        batch_recount=batch_recount, fd_driver=fd_driver,
        use_pallas=use_pallas, sup0=sup0)
    return peelspec.decompose(spec, P, stats, fd_driver=fd_driver)


def _tip_spec_dense(
    gg: BipartiteGraph, batch_recount, stats: PeelStats
) -> PeelSpec:
    """Dense-engine tip spec: masked-MXU batch re-counts (or §5.1
    adaptive incremental pairwise updates) as the CD step, static
    pairwise-butterfly cascade as the FD rule."""
    n = gg.n_u
    _dense_guard(gg.n_u, gg.n_v)
    A = jnp.asarray(gg.adjacency())
    wedge_w = np.asarray(counting.vertex_wedge_workload(A))  # paper's proxy

    support = counting.vertex_butterflies(A)
    counting.assert_exact(support)
    sup0 = np.rint(np.asarray(support)).astype(np.int64)

    # counting-work bound ∧cnt (alg.1 complexity) for the adaptive rule
    du, dv = gg.degrees()
    cnt_bound = float(
        np.minimum(du[gg.edges[:, 0]], dv[gg.edges[:, 1]]).sum())

    # Static pairwise butterfly matrix for the incremental path.
    pair_bf_full = None
    if batch_recount is not True:
        W = np.array(counting.wedge_counts(A))
        np.fill_diagonal(W, 0)
        pair_bf_full = jnp.asarray(W * (W - 1) / 2)

    state = dict(alive=jnp.ones((n,), dtype=bool), support=support)

    def cd_step(active: np.ndarray) -> np.ndarray:
        state["alive"] = state["alive"] & jnp.asarray(~active)
        if batch_recount is True:
            use_recount = True
        elif batch_recount is False:
            use_recount = False
        else:  # adaptive §5.1: peel-work vs recount-work
            use_recount = float(wedge_w[active].sum()) > cnt_bound
        if use_recount:
            state["support"] = _tip_recount(A, state["alive"])
            stats.recounts += 1
        else:
            state["support"] = state["support"] - _tip_fd_delta(
                pair_bf_full, jnp.asarray(active)
            )
            stats.updates += int(active.sum()) * int(
                np.asarray(state["alive"]).sum())
        return np.rint(np.asarray(state["support"])).astype(np.int64)

    A_np = np.asarray(A)

    def fd_partition(i, part, sup_init, theta, fd_driver):
        rows = np.where(part == i)[0]
        if rows.size == 0:
            return 0, 0, 0
        rounds = _tip_fd_peel(A_np, rows, sup_init[rows], theta, int(i))
        return rounds, 0, 0

    return PeelSpec(
        kind="tip", n=n, sup0=sup0,
        workload=lambda s: wedge_w,
        est=lambda s: wedge_w,
        cd_step=cd_step,
        fd_partition=fd_partition,
    )


def _tip_fd_peel(
    A_np: np.ndarray, rows: np.ndarray, sup0: np.ndarray,
    theta: np.ndarray, part_i: int = 0,
) -> int:
    """Sequential (level-synchronous) bottom-up peel of one partition.

    Exact because a butterfly has exactly two U-endpoints and V is never
    peeled: pairwise counts within the partition are static.
    """
    Ai = jnp.asarray(A_np[rows])
    W = np.array(counting.wedge_counts(Ai))
    np.fill_diagonal(W, 0)
    pair_bf = jnp.asarray(W * (W - 1) / 2)

    s = rows.size
    alive = np.ones(s, dtype=bool)
    support = sup0.astype(np.float64).copy()
    col = obs.active_collector()
    trows: list = []
    k = 0
    rounds = 0
    while alive.any():
        k = max(k, int(support[alive].min()))
        while True:
            S = alive & (support <= k)
            if not S.any():
                break
            theta[rows[S]] = k
            alive &= ~S
            delta = np.asarray(_tip_fd_delta(pair_bf, jnp.asarray(S)))
            support -= delta
            rounds += 1
            if col is not None:
                trows.append(dict(k=k, died=int(S.sum()),
                                  frontier=int(alive.sum())))
    if col is not None:
        col.record_fd_host(part_i, trows)
    return rounds


# =====================================================================
# Tip decomposition, csr engine (sparse wedge list, core/csr.py)
# =====================================================================
def _tip_spec_csr(
    gg: BipartiteGraph, stats: PeelStats, use_pallas: bool = False,
    sup0: Optional[np.ndarray] = None,
    wed: Optional[csr.Wedges] = None,
) -> PeelSpec:
    """csr-engine tip spec: CD + FD on the U pair list — no dense
    matrices anywhere.

    The pair list (U pairs with a common neighbour, and how many) comes
    from ``csr.tip_pairs``: one int8 adjacency product on the MXU where
    ``csr.pair_source`` finds it fitting and cheaper, else the host
    wedge list; ``wed`` injects a prebuilt wedge or pair list.  Its
    build is the ``tip.pairs`` span (args ``source``, ``rows``, ``k``,
    ``pairs``, ``bits``).  Support init and every update are exact
    integer ``segment_sum``s over the pairs; pair butterfly counts are
    static because the V side is never peeled, so the engine is purely
    incremental (zero re-counts).  Counts are int32, or int64 under
    ``csr.count_scope`` where ``csr.count_bits`` finds ⋈init above
    int32; the Pallas route carries f32/int32 counts and refuses the
    latter.  ``use_pallas`` routes the CD delta through the blocked
    row-sum kernel over the vertex-major slot layout
    (:func:`csr.tip_delta_slots`)."""
    n = gg.n_u
    with obs.span("tip.pairs", cat="init") as sp:
        if wed is None:
            pairs = csr.tip_pairs(gg)
        else:
            pairs = wed if isinstance(wed, csr.TipPairs) else \
                csr.TipPairs.of(wed)
        pair_bf0 = pairs.pair_butterflies0()
        sup_np = (csr.vertex_butterflies_csr(pairs) if sup0 is None
                  else np.asarray(sup0, dtype=np.int64))
        bits = csr.count_bits(sup_np, pair_bf0)
        if sp is not None:
            sp.update(source=pairs.source, rows=pairs.rows, k=pairs.k,
                      pairs=pairs.n_pairs, bits=bits)
    stats.pair_source, stats.pair_dtype = pairs.source, pairs.dtype
    stats.pair_rows, stats.pair_k = pairs.rows, pairs.k
    stats.count_bits = bits
    if bits == 64 and use_pallas:
        raise OverflowError(
            "tip supports exceed int32 and the Pallas tip kernel carries "
            "f32/int32 counts; run without use_pallas, whose "
            "segment_sum path widens them to int64")
    cdt = np.int64 if bits == 64 else np.int32
    scope = partial(csr.count_scope, bits)
    with scope():
        pa = jnp.asarray(pairs.pair_a)
        pb = jnp.asarray(pairs.pair_b)
        pbf = jnp.asarray(pair_bf0.astype(cdt))
        state = dict(support=jnp.asarray(sup_np.astype(cdt)))
    wu, _ = csr.wedge_workload(gg)
    wedge_w = wu.astype(np.float64)

    if use_pallas:
        slots = csr.pack_tip_slots(pairs, pair_bf0, sup=sup_np)
        slot_partner = jnp.asarray(slots["partner"])
        slot_bf = jnp.asarray(slots["bf"])

    def cd_step(active: np.ndarray) -> np.ndarray:
        with scope():
            if use_pallas:
                delta = csr.tip_delta_slots(
                    jnp.asarray(active), slot_partner, slot_bf, n)
            else:
                delta = csr.tip_delta_csr(
                    jnp.asarray(active), pa, pb, pbf, n)
            state["support"] = state["support"] - delta
            out = np.asarray(state["support"]).astype(np.int64)
        if pairs.n_pairs:
            stats.updates += int(
                np.count_nonzero(active[pairs.pair_a] | active[pairs.pair_b])
            )
        return out

    def fd_partition(i, part, sup_init, theta, fd_driver):
        with scope():
            rounds = _tip_fd_csr(pairs, pair_bf0, part, i, sup_init, theta,
                                 fd_driver=fd_driver, dtype=cdt)
        return rounds, 0, 0

    def fd_vmapped(part, sup_init, theta, n_parts):
        with scope():
            rounds = _tip_fd_vmapped_csr(
                pairs, pair_bf0, part, sup_init, theta, n_parts,
                dtype=cdt)
        return rounds, 0

    return PeelSpec(
        kind="tip", n=n, sup0=sup_np,
        workload=lambda s: wedge_w,
        est=lambda s: wedge_w,
        cd_step=cd_step,
        fd_partition=fd_partition,
        fd_vmapped=fd_vmapped,
        pairs=pairs,
    )


def _tip_fd_csr(
    wed: csr.TipPairs,
    pair_bf0: np.ndarray,
    part: np.ndarray,
    i: int,
    sup_init: np.ndarray,
    theta: np.ndarray,
    fd_driver: str = "device",
    dtype=np.int32,
) -> int:
    """Bottom-up peel of partition i on the pair list.

    Only pairs with both endpoints inside the partition matter: vertices
    of later partitions are never peeled during FD_i, and deltas to them
    are discarded anyway.

    ``fd_driver="device"`` (default) runs the whole cascade in one
    ``lax.while_loop`` (:func:`_fd_tip_device`) — a single dispatch per
    partition, zero host round-trips.  ``"host"`` keeps the per-round
    dispatch loop (the PR-1 baseline, benchmarked against).  ``dtype``
    is the device count width (int64 inside ``csr.count_scope(64)``).
    """
    with obs.span("fd.pack", cat="fd.pack"):
        mine = part == i
        if not mine.any():
            return 0
        n = part.size
        mask = (mine[wed.pair_a] & mine[wed.pair_b] if wed.n_pairs
                else np.zeros(0, bool))

        support0 = np.zeros(n, dtype=np.int64)
        support0[mine] = sup_init[mine]
        if fd_driver == "device":
            # bucket-pad the pair arrays so the while_loop compiles once
            # per size bucket, not once per partition
            size = _bucket_pad(int(mask.sum()))
            args = (
                jnp.asarray(mine), jnp.asarray(support0.astype(dtype)),
                jnp.asarray(_pad_zeros(wed.pair_a[mask], size)),
                jnp.asarray(_pad_zeros(wed.pair_b[mask], size)),
                jnp.asarray(_pad_zeros(pair_bf0[mask].astype(dtype),
                                       size)),
                n,
            )
        else:
            pa = jnp.asarray(wed.pair_a[mask])
            pb = jnp.asarray(wed.pair_b[mask])
            pbf = jnp.asarray(pair_bf0[mask].astype(dtype))

    cap = obs.fd_ring_cap()
    if fd_driver == "device":
        if cap:
            theta_d, rounds, _, rings = _fd_tip_device_rings(
                *args, ring_cap=cap)
            _drain_rings("device", [i], [int(rounds)], rings, cap)
        else:
            theta_d, rounds, _ = _fd_tip_device(*args)
        theta_np = np.asarray(theta_d).astype(np.int64)
        theta[mine] = theta_np[mine]
        return int(rounds)

    def peel(S, sup):
        delta = np.asarray(
            csr.tip_delta_csr(jnp.asarray(S), pa, pb, pbf, n)
        ).astype(np.int64)
        return sup - delta

    col = obs.active_collector()
    if col is None:
        return _fd_cascade(mine, support0, theta, peel)
    rows: list = []
    rounds = _fd_cascade(
        mine, support0, theta, peel,
        on_round=lambda k, died, frontier: rows.append(
            dict(k=k, died=died, frontier=frontier)))
    col.record_fd_host(i, rows)
    return rounds


def _tip_fd_vmapped_csr(
    wed: csr.TipPairs,
    pair_bf0: np.ndarray,
    part: np.ndarray,
    sup_init: np.ndarray,
    theta: np.ndarray,
    n_parts: int,
    dtype=np.int32,
) -> np.ndarray:
    """Single-dispatch tip Phase 2: pack all partitions into one stacked
    shape-bucketed layout and peel them in ONE batched while_loop
    (:func:`_fd_tip_vmapped`).  Writes θ in place; returns the (B,)
    per-partition round counts (bit-identical to the per-partition
    drivers — same cascade, one dispatch)."""
    if n_parts == 0:
        return np.zeros(0, dtype=np.int64)
    from .distributed import pack_fd_partitions_tip_csr

    packed = pack_fd_partitions_tip_csr(
        wed, pair_bf0, part, sup_init, n_parts, bucket=True, dtype=dtype)
    args = tuple(jnp.asarray(packed[k])
                 for k in ("pa", "pb", "bf", "mine", "sup0"))
    cap = obs.fd_ring_cap()
    if cap:
        theta_st, rounds, _, rings = _fd_tip_vmapped_rings(
            *args, ring_cap=cap)
    else:
        theta_st, rounds, _ = _fd_tip_vmapped(*args)
    mm = packed["mine"]
    theta[packed["gids"][mm]] = np.asarray(theta_st).astype(np.int64)[mm]
    rounds_np = np.asarray(rounds).astype(np.int64)
    if cap:
        _drain_rings("vmapped", list(range(rounds_np.size)),
                     rounds_np.tolist(), rings, cap)
    return rounds_np


def _wing_fd_vmapped_csr(
    wed: csr.Wedges,
    part: np.ndarray,
    sup_init: np.ndarray,
    theta: np.ndarray,
    n_parts: int,
    use_pallas: bool = False,
) -> Tuple[np.ndarray, int]:
    """Single-dispatch wing Phase 2 (see :func:`_tip_fd_vmapped_csr`).

    ``use_pallas`` swaps the vmapped segment-sum body for the blocked
    Pallas ``support_update`` kernel over the stacked slot layout
    (:func:`_fd_wing_vmapped_pallas`) — interpret mode off-TPU, θ and
    round/update counts parity-locked either way.  Returns (rounds (B,),
    update count)."""
    if n_parts == 0:
        return np.zeros(0, dtype=np.int64), 0
    from .distributed import pack_fd_partitions_csr

    packed = pack_fd_partitions_csr(
        wed, part, sup_init, n_parts, bucket=True,
        flat=not use_pallas, slots=use_pallas,
    )
    cap = obs.fd_ring_cap()
    rings = None
    if use_pallas:
        from repro.kernels import ops as kops  # local: keep core light

        R, _ = packed["slot_sizes"]
        W0 = packed["W0"]
        W_rows = np.zeros((n_parts, R), dtype=np.int32)
        w = min(R, W0.shape[1])
        W_rows[:, :w] = W0[:, :w]
        args = (jnp.asarray(packed["slot_e1"]),
                jnp.asarray(packed["slot_e2"]),
                jnp.asarray(packed["slot_valid"]), jnp.asarray(W_rows),
                jnp.asarray(packed["mine"]), jnp.asarray(packed["sup0"]))
        interpret = kops.default_interpret()
        if cap:
            theta_st, rounds, nupd, rings = _fd_wing_vmapped_pallas_rings(
                *args, interpret=interpret, ring_cap=cap)
        else:
            theta_st, rounds, nupd = _fd_wing_vmapped_pallas(
                *args, interpret=interpret)
    else:
        args = tuple(jnp.asarray(packed[k]) for k in (
            "flat_we1", "flat_we2", "flat_wp", "flat_alive0", "flat_W0",
            "mine", "sup0"))
        n_pairs = int(packed["flat_W0"].shape[0])
        if cap:
            theta_st, rounds, nupd, rings = _fd_wing_vmapped_rings(
                *args, n_pairs=n_pairs, ring_cap=cap)
        else:
            theta_st, rounds, nupd = _fd_wing_vmapped(
                *args, n_pairs=n_pairs)
    mm = packed["mine"]
    theta[packed["gids"][mm]] = np.asarray(theta_st).astype(np.int64)[mm]
    rounds_np = np.asarray(rounds).astype(np.int64)
    if rings is not None:
        _drain_rings("vmapped", list(range(rounds_np.size)),
                     rounds_np.tolist(), rings, cap)
    return rounds_np, int(nupd)


# =====================================================================
# Wing decomposition (edge peeling)
# =====================================================================
@partial(jax.jit, static_argnames=("shape",))
def _wing_recount(shape, edges: jax.Array, alive_e: jax.Array) -> jax.Array:
    A = counting.masked_adjacency(shape, edges, alive_e)
    return counting.edge_butterflies(A, edges)


def _wing_links(be: BEIndex):
    return (
        jnp.asarray(be.link_edge),
        jnp.asarray(be.link_twin),
        jnp.asarray(be.link_bloom),
    )


@partial(jax.jit, static_argnames=("nb", "m"))
def _wing_update(
    peeled_e: jax.Array,
    alive_link: jax.Array,
    k_alive: jax.Array,
    support: jax.Array,
    le: jax.Array,
    lt: jax.Array,
    lb: jax.Array,
    nb: int,
    m: int,
):
    """Batched BE-Index support update (alg.6 exact semantics).

    Bloom bookkeeping: a twin *pair* dies when either member is peeled.
    Dying-pair survivors (widows) lose every butterfly they had in the
    bloom (k_alive − 1); edges of surviving pairs lose one butterfly per
    dying pair (c_B).  ``segment_sum`` replaces the paper's atomics.
    """
    pe = peeled_e[le]
    pt = peeled_e[lt]
    pair_dies = alive_link & (pe | pt)
    canon = le < lt
    c = jax.ops.segment_sum(
        (pair_dies & canon).astype(jnp.int32), lb, num_segments=nb
    )
    widow = alive_link & ~pe & pt
    surv = alive_link & ~pair_dies
    contrib = jnp.where(widow, k_alive[lb] - 1, 0) + jnp.where(
        surv, c[lb], 0
    )
    loss = jax.ops.segment_sum(contrib, le, num_segments=m)
    support = support - loss
    k_alive = k_alive - c
    alive_link = alive_link & ~pair_dies
    n_updates = jnp.sum(widow.astype(jnp.int32)) + jnp.sum(
        (surv & (c[lb] > 0)).astype(jnp.int32)
    )
    return alive_link, k_alive, support, n_updates


def wing_decomposition(
    g: BipartiteGraph,
    P: int = 16,
    engine: str = "beindex",
    be: Optional[BEIndex] = None,
    fd_driver: str = "device",
    use_pallas: bool = False,
    sup0: Optional[np.ndarray] = None,
) -> PeelResult:
    """PBNG wing decomposition (§3.3) — θ per edge.

    ``engine``/``fd_driver`` matrix (all combinations θ-bit-identical):

    ========  =====================================  ====================
    engine    support counting / update              fd_driver
    ========  =====================================  ====================
    beindex   BE-Index widow/survivor (alg. 4/6)     (host cascade)
    dense     masked MXU matmul re-counts, O(n²)     (host cascade)
    csr       incremental wedge-list updates         device │ vmapped │ host
    ========  =====================================  ====================

    Example::

        from repro.core import random_bipartite, wing_decomposition
        g = random_bipartite(1000, 800, 8000, seed=0)
        res = wing_decomposition(g, engine="csr", fd_driver="vmapped")
        print(res.theta.max(), res.stats.sync_reduction)

    ``engine`` ∈ {"beindex", "dense", "csr"}: BE-Index incremental
    updates, masked-matmul re-counts, or sparse wedge-list incremental
    updates (``core.csr`` — the scalable path).

    ``fd_driver`` (csr engine only): ``"device"`` (default) peels each FD
    partition in one ``lax.while_loop`` dispatch; ``"vmapped"`` stacks
    ALL partitions into one shape-bucketed layout and runs the whole
    Phase 2 as ONE batched while_loop — a single dispatch total, the
    paper's "no global synchronization" stated structurally for the
    entire fine-grained phase; ``"host"`` keeps the per-round python
    loop as an A/B baseline.  All drivers produce bit-identical θ and
    identical per-partition round/update counts.

    ``use_pallas`` (csr engine only): run CD support updates through the
    blocked ``kernels.support_update`` Pallas kernel on the pairs-major
    slot layout (interpret mode off-TPU) instead of flat segment_sums.
    With ``fd_driver="vmapped"`` the same kernel also runs INSIDE the FD
    while_loop body over the stacked partition slot layout (one kernel
    launch per round covering every partition)."""
    stats = PeelStats(
        engine=engine,
        fd_driver=fd_driver if engine == "csr" else "host",
    )
    spec = build_peel_spec(
        g, "wing", stats, engine=engine, be=be, fd_driver=fd_driver,
        use_pallas=use_pallas, sup0=sup0)
    return peelspec.decompose(spec, P, stats, fd_driver=fd_driver)


def _wing_workload_est():
    """Wing's range/estimate weights: workload proxy for edges = current
    support (§3.3.2); partition estimates read the same supports."""
    return (lambda s: np.maximum(s, 1), lambda s: s)


def _wing_spec_beindex(
    g: BipartiteGraph, be: Optional[BEIndex], stats: PeelStats
) -> PeelSpec:
    """BE-Index wing spec: alg.4/6 widow/survivor updates as the CD
    step, link-packed sub-indices (alg.5) as the FD rule."""
    m = g.m
    if be is None:
        be = build_beindex(g)
    le, lt, lb = _wing_links(be)
    nb = max(be.nb, 1)
    state = dict(
        alive_link=jnp.ones((be.n_links,), dtype=bool),
        k_alive=jnp.asarray(be.bloom_k.astype(np.int32)),
        support=jnp.asarray(be.edge_support(m).astype(np.int32)),
    )
    sup0 = np.rint(np.asarray(state["support"])).astype(np.int64)

    def cd_step(active: np.ndarray) -> np.ndarray:
        state["alive_link"], state["k_alive"], state["support"], nupd = (
            _wing_update(
                jnp.asarray(active), state["alive_link"], state["k_alive"],
                state["support"], le, lt, lb, nb, m,
            )
        )
        stats.updates += int(nupd)
        return np.rint(np.asarray(state["support"])).astype(np.int64)

    def fd_partition(i, part, sup_init, theta, fd_driver):
        rounds, nupd = _wing_fd_beindex(g, be, part, i, sup_init, theta)
        return rounds, nupd, 0

    workload, est = _wing_workload_est()
    return PeelSpec(
        kind="wing", n=m, sup0=sup0, workload=workload, est=est,
        cd_step=cd_step, fd_partition=fd_partition,
    )


def _wing_spec_dense(
    g: BipartiteGraph, stats: PeelStats,
    sup0: Optional[np.ndarray] = None,
) -> PeelSpec:
    """Dense wing spec: masked-MXU batch re-counts for both phases."""
    m = g.m
    _dense_guard(g.n_u, g.n_v)
    edges = jnp.asarray(g.edges.astype(np.int32))
    shape = (g.n_u, g.n_v)
    if sup0 is None:
        support = _wing_recount(shape, edges, jnp.ones((m,), dtype=bool))
        counting.assert_exact(support)
        sup0 = np.rint(np.asarray(support)).astype(np.int64)
    else:
        sup0 = np.asarray(sup0, dtype=np.int64)
    state = dict(alive=np.ones(m, dtype=bool))

    def cd_step(active: np.ndarray) -> np.ndarray:
        state["alive"] &= ~active
        sup = _wing_recount(shape, edges, jnp.asarray(state["alive"]))
        stats.recounts += 1
        return np.rint(np.asarray(sup)).astype(np.int64)

    def fd_partition(i, part, sup_init, theta, fd_driver):
        rounds, nrec = _wing_fd_dense(g, part, i, sup_init, theta)
        return rounds, 0, nrec

    workload, est = _wing_workload_est()
    return PeelSpec(
        kind="wing", n=m, sup0=sup0, workload=workload, est=est,
        cd_step=cd_step, fd_partition=fd_partition,
    )


def _wing_spec_csr(
    g: BipartiteGraph, stats: PeelStats, use_pallas: bool = False,
    sup0: Optional[np.ndarray] = None,
    wed: Optional[csr.Wedges] = None,
) -> PeelSpec:
    """csr wing spec: incremental wedge-list widow/survivor updates as
    the CD step (optionally through the blocked Pallas kernel on the
    pairs-major slot layout), touching-wedge packed lists as the FD
    rule."""
    m = g.m
    if wed is None:
        wed = csr.build_wedges(g)
    we1 = jnp.asarray(wed.wedge_e1)
    we2 = jnp.asarray(wed.wedge_e2)
    wpj = jnp.asarray(wed.wedge_pair)
    n_pairs = wed.n_pairs
    sup0 = (csr.edge_butterflies0(wed) if sup0 is None
            else np.asarray(sup0, dtype=np.int64))
    if sup0.size and int(sup0.max()) > 2 ** 31 - 1:
        raise OverflowError("wing supports exceed int32, the width of the "
                            "wing path's device counts")
    stats.count_bits = 32
    state = dict(
        alive_w=jnp.ones((wed.n_wedges,), dtype=bool),
        Wp=csr.pair_wedge_counts(wed),
        support=jnp.asarray(sup0.astype(np.int32)),
    )
    if use_pallas:
        slots = csr.pack_update_slots(wed)
        state["alive_slots"] = jnp.asarray(slots["valid"])
        slot_e1 = jnp.asarray(slots["e1"])
        slot_e2 = jnp.asarray(slots["e2"])

    def cd_step(active: np.ndarray) -> np.ndarray:
        if use_pallas:
            state["alive_slots"], state["Wp"], state["support"], nupd = (
                csr.wing_update_slots(
                    jnp.asarray(active), state["alive_slots"], state["Wp"],
                    state["support"], slot_e1, slot_e2, n_pairs, m,
                )
            )
        else:
            state["alive_w"], state["Wp"], state["support"], nupd = (
                csr.wing_update_csr(
                    jnp.asarray(active), state["alive_w"], state["Wp"],
                    state["support"], we1, we2, wpj, n_pairs, m,
                )
            )
        stats.updates += int(nupd)
        return np.rint(np.asarray(state["support"])).astype(np.int64)

    def fd_partition(i, part, sup_init, theta, fd_driver):
        rounds, nupd = _wing_fd_csr(
            wed, part, i, sup_init, theta, fd_driver=fd_driver)
        return rounds, nupd, 0

    def fd_vmapped(part, sup_init, theta, n_parts):
        return _wing_fd_vmapped_csr(
            wed, part, sup_init, theta, n_parts, use_pallas=use_pallas)

    workload, est = _wing_workload_est()
    return PeelSpec(
        kind="wing", n=m, sup0=sup0, workload=workload, est=est,
        cd_step=cd_step, fd_partition=fd_partition, fd_vmapped=fd_vmapped,
    )


def _wing_fd_dense(
    g: BipartiteGraph,
    part: np.ndarray,
    i: int,
    sup_init: np.ndarray,
    theta: np.ndarray,
) -> Tuple[int, int]:
    """FD for partition i, dense engine: peel E_i inside the ≥i subgraph,
    re-counting supports on the masked adjacency each round."""
    sel = np.where(part >= i)[0]
    mine = part[sel] == i
    if not mine.any():
        return 0, 0
    sub_edges = jnp.asarray(g.edges[sel].astype(np.int32))
    shape = (g.n_u, g.n_v)

    alive = np.ones(sel.size, dtype=bool)
    support = sup_init[sel].astype(np.int64).copy()
    col = obs.active_collector()
    trows: list = []
    k = 0
    rounds = 0
    recounts = 0
    while (alive & mine).any():
        k = max(k, int(support[alive & mine].min()))
        while True:
            S = alive & mine & (support <= k)
            if not S.any():
                break
            theta[sel[S]] = k
            alive &= ~S
            sup = _wing_recount(shape, sub_edges, jnp.asarray(alive))
            recounts += 1
            support = np.rint(np.asarray(sup)).astype(np.int64)
            rounds += 1
            if col is not None:
                trows.append(dict(k=k, died=int(S.sum()),
                                  frontier=int((alive & mine).sum())))
    if col is not None:
        col.record_fd_host(int(i), trows)
    return rounds, recounts


def _wing_fd_csr(
    wed: csr.Wedges,
    part: np.ndarray,
    i: int,
    sup_init: np.ndarray,
    theta: np.ndarray,
    fd_driver: str = "device",
) -> Tuple[int, int]:
    """FD for partition i, csr engine.

    Sub-structure = the ≥i induced subgraph (the same one the dense FD
    re-counts on): per-pair alive counts W_p are re-derived over ALL ≥i
    wedges, but the wedge *list* carries only the wedges touching
    partition i — later-partition-only wedges never die during FD_i and
    their survivor charges land on edges whose deltas are discarded
    anyway (their FD runs from its own ⋈init snapshot).

    ``fd_driver="device"`` (default) runs the whole cascade in
    ``lax.while_loop`` launches that shrink the wedge list as its wedges
    die (:func:`_fd_wing_compacting`; the obs timeline keeps the single
    launch of :func:`_fd_wing_device_rings`); ``"host"`` keeps the
    per-round dispatch loop (the PR-1 baseline, benchmarked against).
    """
    with obs.span("fd.pack", cat="fd.pack"):
        mine = part == i
        if not mine.any():
            return 0, 0
        m = part.size
        n_pairs = wed.n_pairs
        if wed.n_wedges:
            p1 = part[wed.wedge_e1]
            p2 = part[wed.wedge_e2]
            keep_ge = (p1 >= i) & (p2 >= i)
            # only wedges TOUCHING partition i can die during FD_i; the
            # untouched ≥i wedges stay alive all phase and their survivor
            # charges land on discarded later-partition edges — fold them
            # into the static W_p init instead of carrying them (exact;
            # see distributed.pack_fd_partitions_csr)
            keep = keep_ge & (np.minimum(p1, p2) == i)
        else:
            keep_ge = keep = np.zeros(0, bool)
        Wp = jnp.asarray(
            np.bincount(
                wed.wedge_pair[keep_ge], minlength=max(n_pairs, 1)
            ).astype(np.int32)
        )

        support_full = np.zeros(m, dtype=np.int64)
        support_full[mine] = sup_init[mine]
        if fd_driver == "device":
            # bucket-pad the wedge arrays (dead zero wedges are inert) so
            # the while_loop compiles once per size bucket
            n_kept = int(keep.sum())
            size = _bucket_pad(n_kept)
            alive_w = np.zeros(size, dtype=bool)
            alive_w[:n_kept] = True
            args = (
                jnp.asarray(mine), jnp.asarray(support_full.astype(np.int32)),
                jnp.asarray(alive_w), Wp,
                jnp.asarray(_pad_zeros(wed.wedge_e1[keep], size)),
                jnp.asarray(_pad_zeros(wed.wedge_e2[keep], size)),
                jnp.asarray(_pad_zeros(wed.wedge_pair[keep], size)),
                n_pairs, m,
            )
        else:
            kwe1 = jnp.asarray(wed.wedge_e1[keep])
            kwe2 = jnp.asarray(wed.wedge_e2[keep])
            kwp = jnp.asarray(wed.wedge_pair[keep])
            alive_w = jnp.ones((int(keep.sum()),), dtype=bool)
            support = jnp.asarray(support_full.astype(np.int32))

    cap = obs.fd_ring_cap()
    if fd_driver == "device":
        if cap:
            theta_d, rounds, nupd, rings = _fd_wing_device_rings(
                *args, ring_cap=cap)
            _drain_rings("device", [i], [int(rounds)], rings, cap)
        else:
            theta_d, rounds, nupd = _fd_wing_compacting(*args, part=i)
        theta_np = np.asarray(theta_d).astype(np.int64)
        theta[mine] = theta_np[mine]
        return int(rounds), int(nupd)

    nupd = 0

    def peel(S, sup):
        nonlocal alive_w, Wp, support, nupd
        alive_w, Wp, support, nu = csr.wing_update_csr(
            jnp.asarray(S), alive_w, Wp, support,
            kwe1, kwe2, kwp, n_pairs, m,
        )
        nupd += int(nu)
        return np.asarray(support).astype(np.int64)

    col = obs.active_collector()
    if col is None:
        rounds = _fd_cascade(mine, support_full, theta, peel)
        return rounds, nupd
    rows: list = []
    upds: list = []
    last = dict(n=0)

    def on_round(k, died, frontier):
        rows.append(dict(k=k, died=died, frontier=frontier))
        upds.append(nupd - last["n"])
        last["n"] = nupd

    rounds = _fd_cascade(mine, support_full, theta, peel,
                         on_round=on_round)
    col.record_fd_host(i, rows, updates=upds)
    return rounds, nupd


def _wing_fd_beindex(
    g: BipartiteGraph,
    be: BEIndex,
    part: np.ndarray,
    i: int,
    sup_init: np.ndarray,
    theta: np.ndarray,
) -> Tuple[int, int]:
    """FD for partition i, BE-Index engine (alg.5 semantics).

    Sub-index = links whose pair touches partition i with both members in
    partitions ≥ i; bloom numbers initialised to the count of pairs with
    both members ≥ i (alg.5 lines 21-24).
    """
    ple = part[be.link_edge]
    plt_ = part[be.link_twin]
    pair_min = np.minimum(ple, plt_)
    pair_ge = (ple >= i) & (plt_ >= i)
    keep = pair_ge & (pair_min == i)          # pairs that can die in FD_i
    if not keep.any():
        return 0, 0

    canon_full = be.link_edge < be.link_twin
    # bloom number in I_i: pairs with both members ≥ i
    k_init = np.zeros(be.nb, dtype=np.int64)
    np.add.at(k_init, be.link_bloom[pair_ge & canon_full], 1)

    le = jnp.asarray(be.link_edge[keep])
    lt = jnp.asarray(be.link_twin[keep])
    lb = jnp.asarray(be.link_bloom[keep])
    nb = max(be.nb, 1)
    m = g.m

    alive_link = jnp.ones((int(keep.sum()),), dtype=bool)
    k_alive = jnp.asarray(k_init.astype(np.int32))
    support_full = np.zeros(m, dtype=np.int64)
    mine_idx = np.where(part == i)[0]
    support_full[mine_idx] = sup_init[mine_idx]
    support = jnp.asarray(support_full.astype(np.int32))

    mine = part == i
    nupd = 0

    def peel(S, sup):
        nonlocal alive_link, k_alive, support, nupd
        alive_link, k_alive, support, nu = _wing_update(
            jnp.asarray(S), alive_link, k_alive, support,
            le, lt, lb, nb, m,
        )
        nupd += int(nu)
        return np.asarray(support).astype(np.int64)

    col = obs.active_collector()
    if col is None:
        rounds = _fd_cascade(mine, support_full.copy(), theta, peel)
        return rounds, nupd
    rows: list = []
    upds: list = []
    last = dict(n=0)

    def on_round(k, died, frontier):
        rows.append(dict(k=k, died=died, frontier=frontier))
        upds.append(nupd - last["n"])
        last["n"] = nupd

    rounds = _fd_cascade(mine, support_full.copy(), theta, peel,
                         on_round=on_round)
    col.record_fd_host(i, rows, updates=upds)
    return rounds, nupd


# =====================================================================
# Baseline: level-synchronous bottom-up peeling round count
# =====================================================================
def bup_levels(theta: np.ndarray) -> int:
    """Number of peeling iterations a level-by-level parallel BUP
    (ParButterfly) needs — its synchronization count ρ (paper footnote 6
    approximates this by FD round counts; exact value = Σ over levels of
    cascade rounds, lower-bounded by #distinct levels)."""
    return int(np.unique(theta).size)


# =====================================================================
# Baseline: BE_PC — progressive-compression peeling (Wang et al. [67])
# =====================================================================
def wing_decomposition_bepc(
    g: BipartiteGraph, tau: float = 0.25
) -> Tuple[np.ndarray, PeelStats]:
    """Top-down progressive compression (the paper's strongest baseline,
    table 3's BE_PC row).

    Descending support thresholds t: extract the maximal subgraph whose
    edges keep ≥ t butterflies (a t-wing superset — everything with
    θ ≥ t), resolve it by bottom-up peeling *within the subgraph*, then
    move down.  High-θ edges never receive updates from low-θ peels —
    the mechanism that made BE_PC state-of-the-art pre-PBNG.

    Dense-recount formulation; exact vs the oracle (tests).
    """
    m = g.m
    edges = jnp.asarray(g.edges.astype(np.int32))
    shape = (g.n_u, g.n_v)
    stats = PeelStats()

    def recount(mask: np.ndarray) -> np.ndarray:
        stats.recounts += 1
        sup = _wing_recount(shape, edges, jnp.asarray(mask))
        return np.rint(np.asarray(sup)).astype(np.int64)

    theta = np.zeros(m, dtype=np.int64)
    resolved = np.zeros(m, dtype=bool)
    sup0 = recount(np.ones(m, bool))
    t = max(int(sup0.max()), 1)
    thresholds = []
    while t > 1:
        thresholds.append(t)
        t = max(1, int(t * tau))
    thresholds.append(1)

    for t in thresholds:
        # ---- candidate core: unresolved edges keeping >= t butterflies
        core = ~resolved
        while True:
            sup = recount(core | resolved)
            bad = core & (sup < t)
            if not bad.any():
                break
            core &= ~bad
        if not core.any():
            continue
        # ---- resolve θ for the core by bottom-up peeling inside
        #      (core ∪ resolved); resolved edges are never peeled
        alive = core | resolved
        peelable = core.copy()
        sup = recount(alive)
        k = t
        while peelable.any():
            k = max(k, int(sup[peelable].min()))
            while True:
                S = peelable & (sup <= k)
                if not S.any():
                    break
                theta[S] = k
                alive &= ~S
                peelable &= ~S
                sup = recount(alive)
                stats.rho_fd_total += 1
        resolved |= core

    theta[~resolved] = 0  # butterfly-free edges
    return theta, stats
