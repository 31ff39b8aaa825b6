"""Entity-agnostic PBNG peeling core.

The paper (§4–§6) defines ONE two-phase peeling algorithm and
instantiates it for two entity universes: vertices (tip, §3.2) and
edges (wing, §3.3).  This module is that algorithm stated once:

* :class:`PeelSpec` — everything entity-specific, reduced to data and
  four callables: the entity universe size, the ⋈init supports, the
  range-selection workload proxy, the incremental CD support update,
  and the FD drivers.
* :func:`cd_loop` — the coarse-grained (Phase 1) driver: adaptive (or
  fixed) range selection + fully-parallel masked peel rounds.  Shared
  verbatim by tip/wing × dense/beindex/csr × single-device/mesh.
* :func:`run_fd` — the fine-grained (Phase 2) dispatcher: LPT partition
  order for the per-partition drivers, or the single-dispatch vmapped
  path.
* :func:`_fd_while_device` / :func:`_fd_while_vmapped` /
  :func:`_fd_cascade` — the THREE cascade driver bodies (one
  ``lax.while_loop`` per partition / one batched ``while_loop`` for the
  whole phase / host loop), each existing exactly once; engines supply
  only their ``update(S, aux)`` rule.

``core.peel`` builds the specs (tip and wing are thin wrappers),
``core.distributed`` reuses :func:`cd_loop` with sharded CD steps and
the same FD bodies under ``shard_map`` — so θ, round counts and update
counts are bit-identical across every instantiation (golden-tested
against the pre-refactor engines in ``tests/test_peelspec_goldens.py``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

__all__ = [
    "PeelStats",
    "PeelResult",
    "PeelSpec",
    "AdaptiveTarget",
    "FixedTarget",
    "cd_loop",
    "run_fd",
    "decompose",
]


# =====================================================================
# Results / stats
# =====================================================================
@dataclasses.dataclass
class PeelStats:
    """Reproduces the paper's evaluation metrics (tables 3/4)."""

    rho_cd: int = 0          # CD global-sync rounds
    rho_fd_total: int = 0    # Σ sequential FD rounds  (≈ ParButterfly's ρ)
    rho_fd_max: int = 0      # FD critical path (what PBNG actually pays)
    updates: int = 0         # support updates applied (beindex engine)
    recounts: int = 0        # batch re-counts (dense engine)
    p_effective: int = 0     # partitions actually created
    engine: str = ""         # engine that produced THESE round counts
    fd_driver: str = ""      # "device" (one while_loop/partition) | "host"
    side: str = ""           # tip: peeled vertex set "u"|"v"; wing: ""
    # tip csr: how the pair list was made ("mxu" | "wedges"), the
    # product's shape as it ran (padding included) and operand dtype
    pair_source: str = ""
    pair_rows: int = 0
    pair_k: int = 0
    pair_dtype: str = ""
    count_bits: int = 0      # csr: device width of supports and θ (32|64)

    @property
    def rho(self) -> int:
        """PBNG synchronization rounds = CD rounds only: FD partitions
        peel with NO global synchronization (the paper's ρ)."""
        return self.rho_cd

    @property
    def sync_reduction(self) -> float:
        """ρ(level-by-level parallel BUP) / ρ(PBNG) — the headline claim.

        ρ(ParB) ≈ total per-level rounds = rho_fd_total (footnote 6).
        Both counts come from *this* run — the ratio is only meaningful
        per engine (an engine's own FD cascade stands in for the
        level-synchronous baseline it would have been).  Benchmarks must
        therefore never mix one engine's rho_cd with another's
        rho_fd_total; :meth:`as_dict` gives them the honest per-engine
        row."""
        return self.rho_fd_total / max(self.rho_cd, 1)

    def as_dict(self) -> dict:
        """Flat JSON-ready view (per-engine rho + derived ratios)."""
        d = dataclasses.asdict(self)
        d["rho"] = self.rho
        d["sync_reduction"] = round(self.sync_reduction, 3)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "PeelStats":
        """Inverse of :meth:`as_dict` — tolerates the derived keys
        (``rho``, ``sync_reduction``) that :meth:`as_dict` appends, so a
        stats row can round-trip through JSON / the hierarchy serializer
        without losing the engine / fd_driver / side provenance tags."""
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


@dataclasses.dataclass
class PeelResult:
    """Everything a decomposition produced.

    ``theta`` are the tip/wing numbers (the deliverable); ``part`` /
    ``ranges`` / ``support_init`` are the CD partition assignment, range
    boundaries θ(1..P+1), and the ⋈init support snapshot — together the
    provenance the hierarchy builder/serializer persists; ``stats`` is
    the engine-tagged :class:`PeelStats` row."""

    theta: np.ndarray        # entity numbers
    part: np.ndarray         # CD partition id per entity
    ranges: np.ndarray       # (P+1,) range boundaries θ(1..P+1)
    support_init: np.ndarray  # ⋈init vector
    stats: PeelStats
    # per-round work curves, present only when the obs layer was
    # enabled during the run (obs.enable(); see docs/OBSERVABILITY.md)
    timeline: Optional["obs.PeelTimeline"] = None
    # tip csr: the pair list CD and FD peeled on (csr.TipPairs), which
    # the hierarchy build reuses instead of recomputing it
    pairs: Optional[object] = None

    def provenance(self) -> dict:
        """Everything besides θ a downstream consumer (the hierarchy
        builder/serializer) needs to reconstruct how this decomposition
        was produced: engine-tagged stats plus the CD partition
        assignment, range boundaries, and ⋈init — together they rebuild
        the peeling order (entities peel by partition, then by θ within
        the partition from the recorded support snapshot).  When a
        timeline was collected its compact digest rides along."""
        prov = dict(
            stats=self.stats.as_dict(),
            part=np.asarray(self.part),
            ranges=np.asarray(self.ranges),
            support_init=np.asarray(self.support_init),
        )
        if self.timeline is not None:
            prov["timeline"] = self.timeline.summary()
        return prov


# =====================================================================
# The spec — one entity universe + its peeling rules
# =====================================================================
@dataclasses.dataclass
class PeelSpec:
    """One PBNG peeling instance, entity-agnostically.

    The two-phase drivers below consume ONLY this interface; tip and
    wing (and every engine of each) differ solely in how they fill it:

    ========== ========================== ===========================
    field      tip instantiation          wing instantiation
    ========== ========================== ===========================
    n          \\|U\\| (or \\|V\\|)       \\|E\\|
    sup0       ⋈ per vertex               ⋈ per edge
    workload   Σ_{v∈N_u} d_v (static)     current support (dynamic)
    est        same wedge workload        ⋈init snapshot
    cd_step    pair-incidence deltas      widow/survivor wedge algebra
    ========== ========================== ===========================

    ``cd_step(active) -> sup_np`` applies one masked peel round to the
    engine's device state and returns the refreshed int64 support
    vector (charging ``stats.updates``/``stats.recounts`` itself).

    ``fd_partition(i, part, sup_init, theta, fd_driver) -> (rounds,
    n_updates, n_recounts)`` peels partition i bottom-up, writing θ in
    place.  ``fd_vmapped(part, sup_init, theta, n_parts) -> (rounds[B],
    n_updates)`` peels ALL partitions in one dispatch (csr engines).

    This is the extension point: a new entity universe (e.g. the
    (r,s)-nucleus generalization) plugs in by building a spec — the CD
    round loop, range selection, LPT scheduling, shape-bucketed packing
    and all three FD cascade drivers are inherited, not re-written.
    """

    kind: str                 # "tip" | "wing" — provenance tag
    n: int                    # entity universe size
    sup0: np.ndarray          # (n,) int64 — ⋈init supports
    workload: Callable        # sup_np -> (n,) range-selection weights
    est: Callable             # sup_np -> (n,) partition workload weights
    cd_step: Callable         # active mask -> refreshed int64 supports
    fd_partition: Optional[Callable] = None
    fd_vmapped: Optional[Callable] = None
    pairs: Optional[object] = None    # tip csr: csr.TipPairs


# =====================================================================
# Range selection (§3.1.3) — host-side histogram + prefix scan
# =====================================================================
def _find_range(
    support: np.ndarray,
    workload: np.ndarray,
    alive: np.ndarray,
    tgt: float,
) -> int:
    """Smallest hi such that Σ workload[alive & support < hi] ≥ tgt."""
    s = support[alive]
    w = workload[alive]
    if s.size == 0:
        return 0
    order = np.argsort(s, kind="stable")
    s, w = s[order], w[order]
    cum = np.cumsum(w)
    pos = int(np.searchsorted(cum, max(tgt, 1e-9)))
    pos = min(pos, s.size - 1)
    return int(s[pos]) + 1


class AdaptiveTarget:
    """Two-way adaptive range targets (§3.1.3)."""

    def __init__(self, total_workload: float, P: int):
        self.P = P
        self.remaining = float(total_workload)
        self.scale = 1.0

    def target(self, i: int) -> float:
        """Workload target for partition i: remaining / remaining parts,
        damped by the last overshoot ratio."""
        rem_parts = max(self.P - i, 1)
        return self.scale * self.remaining / rem_parts

    def consumed(self, initial_estimate: float, final_estimate: float) -> None:
        """Record partition i's actual workload and update the damping."""
        self.remaining = max(self.remaining - final_estimate, 0.0)
        if final_estimate > 0 and initial_estimate > 0:
            # predictive local behaviour: next partition will overshoot
            # roughly like this one did
            self.scale = min(1.0, initial_estimate / final_estimate)


class FixedTarget:
    """Constant total/P range targets — the distributed CD policy
    (supports are already on device; re-estimating per partition buys
    nothing at mesh scale, and θ is partition-invariant anyway)."""

    def __init__(self, total_workload: float, P: int):
        self.tgt = float(total_workload) / max(P, 1)

    def target(self, i: int) -> float:
        """Constant workload target: total / P for every partition."""
        return self.tgt

    def consumed(self, initial_estimate: float, final_estimate: float) -> None:
        """No adaptation — the fixed policy ignores overshoot."""


def _lpt_order(work: np.ndarray) -> np.ndarray:
    """Longest-processing-time order of partitions (fig.4)."""
    return np.argsort(-work, kind="stable")


# =====================================================================
# Phase 1 — the CD round loop (exists once; every engine drives it)
# =====================================================================
def cd_loop(spec: PeelSpec, P: int, stats: PeelStats, target=None):
    """Coarse-grained decomposition: adaptive range selection + masked
    peel rounds until every entity is assigned a partition.

    Returns ``(part, sup_init, ranges, p_effective)``; each inner peel
    round charges ``stats.rho_cd`` (the paper's ρ — the only global
    synchronization points), and the engine's ``cd_step`` charges its
    own update/recount counters.

    With the obs layer on, each partition's range selection is a
    ``cd.select`` span (count == ``p_effective``) and every inner round
    a ``cd.round`` span around a ``cd.step`` span (the engine's
    ``cd_step``: mask upload, update, readback); both counts ==
    ``stats.rho_cd`` by construction.  When a timeline collector is live
    (``obs.maybe_collect``) each round is also recorded into the run's
    timeline.  CD is host-driven, so this is pure host bookkeeping:
    device programs are untouched either way."""
    traced = obs.enabled()
    col = obs.active_collector()
    sup_np = np.asarray(spec.sup0, dtype=np.int64).copy()
    n = sup_np.size
    if target is None:
        target = AdaptiveTarget(float(spec.est(sup_np).sum()), P)
    alive = np.ones(n, dtype=bool)
    part = np.full(n, -1, dtype=np.int32)
    sup_init = np.zeros(n, dtype=np.int64)
    ranges = [0]
    p_eff = 0
    for i in range(P):
        if not alive.any():
            break
        with obs.span("cd.select", cat="cd.select") as sel:
            sup_init[alive] = sup_np[alive]
            if i == P - 1:
                hi = int(sup_np[alive].max()) + 1
            else:
                tgt = target.target(i)
                hi = _find_range(sup_np, spec.workload(sup_np), alive, tgt)
                hi = max(hi, int(sup_np[alive].min()) + 1)  # guarantee progress
            initial_est = float(spec.est(sup_np)[alive & (sup_np < hi)].sum())
            if sel is not None:
                sel.update(part=i, hi=hi)
        ranges.append(hi)

        # ---- inner peeling rounds for range [θ(i), hi)
        while True:
            active = alive & (sup_np < hi)
            if not active.any():
                break
            part[active] = i
            alive &= ~active
            if not traced:
                sup_np = spec.cd_step(active)
            else:
                died = int(active.sum())
                u0, r0 = stats.updates, stats.recounts
                with obs.span("cd.round", cat="cd.round") as sp:
                    with obs.span("cd.step", cat="cd.step") as st:
                        sup_np = spec.cd_step(active)
                        st.update(part=i, died=died)
                    frontier = int(alive.sum())
                    du = stats.updates - u0
                    dr = stats.recounts - r0
                    sp.update(part=i, died=died, frontier=frontier,
                              hi=int(hi), updates=du, recounts=dr)
                if col is not None:
                    col.record_cd_round(i, died, frontier, int(hi), du, dr)
            stats.rho_cd += 1

        final_est = float(spec.est(sup_init)[part == i].sum())
        target.consumed(initial_est, final_est)
        p_eff = i + 1
    stats.p_effective = p_eff
    return part, sup_init, np.asarray(ranges, dtype=np.int64), p_eff


# =====================================================================
# Phase 2 — the FD dispatcher (LPT per-partition / single-dispatch)
# =====================================================================
def run_fd(
    spec: PeelSpec,
    part: np.ndarray,
    sup_init: np.ndarray,
    theta: np.ndarray,
    n_parts: int,
    stats: PeelStats,
    fd_driver: str = "device",
    only: Optional[np.ndarray] = None,
    per_partition: Optional[dict] = None,
) -> None:
    """Fine-grained decomposition over the CD partitions.

    ``fd_driver="vmapped"`` routes through ``spec.fd_vmapped`` (the
    whole phase in one batched while_loop); otherwise partitions run in
    LPT order through ``spec.fd_partition`` (which honours
    ``fd_driver`` = "device" | "host").  Writes θ in place and charges
    the FD round/update/recount counters.

    ``only`` restricts the per-partition path to a subset of partition
    ids (LPT-ordered among themselves) — the streaming repair driver
    (``repro.streaming``) uses it to re-peel just the dirty partitions;
    θ entries of skipped partitions are left untouched so carried-over
    values survive.  ``per_partition``, when given a dict, is filled
    with ``{i: (rounds, updates, recounts)}`` for every partition that
    ran — the cache that lets an incremental run reassemble PeelStats
    bit-identical to a from-scratch re-peel.  Neither knob changes any
    dispatched program: the jitted FD entries are shared verbatim."""
    if n_parts <= 0:
        return
    if fd_driver == "vmapped":
        if only is not None:
            raise ValueError(
                "only= requires a per-partition fd_driver "
                "('device' | 'host'); the vmapped driver dispatches "
                "every partition in one launch")
        with obs.span("fd.vmapped", cat="fd.launch") as sp:
            rounds_v, nupd = spec.fd_vmapped(part, sup_init, theta, n_parts)
            rounds_v = np.asarray(rounds_v)
            if sp is not None:
                sp.update(n_parts=int(n_parts), rounds=int(rounds_v.sum()),
                          updates=int(nupd))
        stats.rho_fd_total = int(rounds_v.sum())
        stats.rho_fd_max = int(rounds_v.max()) if rounds_v.size else 0
        stats.updates += int(nupd)
        return
    if only is None:
        ids = np.arange(n_parts)
    else:
        ids = np.unique(np.asarray(only, dtype=np.int64))
        if ids.size and (ids[0] < 0 or ids[-1] >= n_parts):
            raise ValueError(
                f"only= ids outside [0, {n_parts}): {ids.tolist()}")
    est_w = spec.est(sup_init)
    part_work = np.array(
        [est_w[part == i].sum() for i in ids], dtype=np.float64
    )
    for j in _lpt_order(part_work):
        i = int(ids[j])
        with obs.span("fd.partition", cat="fd.launch") as sp:
            rounds, nupd, nrec = spec.fd_partition(
                i, part, sup_init, theta, fd_driver)
            if sp is not None:
                sp.update(part=i, rounds=int(rounds), updates=int(nupd),
                          recounts=int(nrec))
        if per_partition is not None:
            per_partition[i] = (int(rounds), int(nupd), int(nrec))
        stats.rho_fd_total += rounds
        stats.rho_fd_max = max(stats.rho_fd_max, rounds)
        stats.updates += nupd
        stats.recounts += nrec


def decompose(
    spec: PeelSpec,
    P: int,
    stats: PeelStats,
    fd_driver: str = "device",
    target=None,
) -> PeelResult:
    """Run both phases of one :class:`PeelSpec` and assemble the
    :class:`PeelResult` — THE driver behind ``tip_decomposition`` and
    ``wing_decomposition`` (every engine).

    When the obs layer is enabled this is also the telemetry root: it
    wraps the run in a ``peel`` span with ``cd``/``fd`` phase spans and,
    in timeline mode, installs the timeline collector and attaches the
    built :class:`~repro.obs.PeelTimeline` to the result (synthesizing
    the per-round ``fd.round`` trace events from the drained rings)."""
    with obs.maybe_collect() as col:
        with obs.span("peel.decompose", cat="peel", kind=spec.kind,
                      engine=stats.engine, fd_driver=fd_driver, P=int(P)):
            with obs.span("cd", cat="cd"):
                part, sup_init, ranges, p_eff = cd_loop(
                    spec, P, stats, target=target)
            theta = np.zeros(spec.n, dtype=np.int64)
            with obs.span("fd", cat="fd", driver=fd_driver):
                run_fd(spec, part, sup_init, theta, p_eff, stats,
                       fd_driver=fd_driver)
    timeline = None
    if col is not None:
        timeline = col.build()
        tracer = obs.get_tracer()
        if tracer is not None:
            timeline.emit_trace_events(tracer)
    return PeelResult(
        theta=theta,
        part=part,
        ranges=ranges,
        support_init=sup_init,
        stats=stats,
        timeline=timeline,
        pairs=spec.pairs,
    )


# =====================================================================
# FD cascade drivers — each body exists exactly once
# =====================================================================
def _fd_cascade(mine: np.ndarray, support0: np.ndarray, theta: np.ndarray,
                apply_peel, on_round=None) -> int:
    """Level-synchronous bottom-up cascade shared by the incremental FD
    engines: advance k to the minimum alive support, peel the ≤k set,
    apply the engine's update, repeat until the partition is empty.

    ``apply_peel(S, sup)`` consumes the peel mask and the current int64
    support vector and returns the refreshed one (updating any engine
    state it closes over).  Returns the number of peel rounds.
    ``on_round(k, died, frontier)``, when given, is called after every
    round — the obs layer's host-side stand-in for the device counter
    rings (None, the default, changes nothing).

    This is the *host-loop* driver (one device dispatch per peel round).
    The csr engine defaults to :func:`_fd_while_device`, which runs the
    identical cascade inside a single ``lax.while_loop``.
    """
    alive = mine.copy()
    sup = support0
    k = 0
    rounds = 0
    while alive.any():
        k = max(k, int(sup[alive].min()))
        while True:
            S = alive & (sup <= k)
            if not S.any():
                break
            theta[S] = k
            alive &= ~S
            sup = apply_peel(S, sup)
            rounds += 1
            if on_round is not None:
                on_round(k=k, died=int(S.sum()), frontier=int(alive.sum()))
    return rounds


def _fd_big(sup: jax.Array) -> int:
    """The sentinel for masked-out supports in the k-advance: the largest
    value of ``sup``'s dtype (int32, or int64 where ``csr.count_bits``
    asks for it).  It must be ≥ any real support, else the while_loop can
    never peel the last entities and spins forever."""
    return jnp.iinfo(sup.dtype).max


def _bucket_pad(n: int, floor: int = 128) -> int:
    """Round n up to a quarter-power-of-two bucket (≥ floor) — pads
    per-partition pair / wedge arrays so the jitted FD drivers recompile
    per size *bucket* instead of per partition, with ≤25% padding waste
    (zero padding is algebra-neutral: a pair with 0 butterflies / a dead
    wedge contributes no loss)."""
    if n <= floor:
        return floor
    step = 1 << max(int(n - 1).bit_length() - 2, 0)
    return -(-n // step) * step


def _pad_zeros(x: np.ndarray, size: int) -> np.ndarray:
    if x.size >= size:
        return x
    return np.concatenate([x, np.zeros(size - x.size, dtype=x.dtype)])


def _fd_while_device(mine: jax.Array, sup0: jax.Array, update, aux):
    """The batched FD cascade as one ``lax.while_loop`` — shared by the
    csr tip and wing engines (and the sharded FD bodies in
    ``core.distributed``).

    Semantics are identical to :func:`_fd_cascade` — every iteration
    advances k to the minimum alive support and peels the ≤k set, so the
    round count matches the host driver exactly — but the whole cascade
    stays device-resident: zero host↔device transfers per partition,
    which is the paper's Phase-2 "no global synchronization" property
    stated structurally (one jit'd while_loop, no dispatch per round).

    ``update(S, aux) -> (loss, aux', n_upd)`` is the engine's incremental
    support update; ``aux`` is its loop-carried state (wedge/pair alive
    masks and counts).  Returns (theta, rounds, updates), all on device.
    """

    def cond(state):
        alive, *_ = state
        return jnp.any(alive)

    # derive loop-constant inits from varying inputs so the carry's
    # manual-axes annotation is stable under shard_map (same trick as
    # distributed._fd_body_one_partition)
    zero_e = sup0 * 0
    zero_s = jnp.min(zero_e)
    init = (mine, sup0, aux, zero_e, zero_s, zero_s, zero_s)
    _, _, _, theta, _, rounds, nupd = jax.lax.while_loop(
        cond, partial(_fd_round, update=update), init)
    return theta, rounds, nupd


def _fd_round(state, update):
    """One peel round of :func:`_fd_while_device` on its loop carry
    ``(alive, sup, aux, theta, k, rounds, nupd)`` — also the round of the
    wing driver that relaunches at shrinking wedge sizes
    (``peel._fd_wing_chunk``)."""
    alive, sup, aux, theta, k, rounds, nupd = state
    cur = jnp.where(alive, sup, _fd_big(sup))
    k = jnp.maximum(k, jnp.min(cur))
    S = alive & (sup <= k)
    # S is non-empty whenever alive is (k ≥ min alive support), so every
    # iteration is one real peel round — same count as the host cascade.
    theta = jnp.where(S, k, theta)
    alive = alive & ~S
    loss, aux, nu = update(S, aux)
    return (alive, sup - loss, aux, theta, k, rounds + 1, nupd + nu)


def _fd_while_vmapped(mine: jax.Array, sup0: jax.Array, update, aux):
    """The FULL Phase 2 — every partition's cascade — as ONE batched
    ``lax.while_loop``: the single-dispatch companion of
    :func:`_fd_while_device`.

    ``mine``/``sup0`` carry a leading partition axis [B, E]; each
    iteration advances every still-alive partition by exactly one peel
    round (its own k-advance + ≤k peel), so per-partition round counts
    are bit-identical to the per-partition drivers and the loop's trip
    count is the FD *critical path* rho_fd_max.  Finished partitions
    idle (empty peel sets are algebra-neutral) until the last one
    drains — the whole Phase 2 is one dispatch, zero host round-trips,
    zero collectives: PBNG's "no global synchronization" claim stated
    structurally for the entire fine-grained phase, not per partition.

    ``update(S, aux) -> (loss, aux', n_upd)`` consumes the batched peel
    mask S [B, E] and returns batched losses plus the scalar update
    count of the round.  Returns (theta [B, E], rounds [B], updates).
    """

    def cond(state):
        alive, *_ = state
        return jnp.any(alive)

    def body(state):
        alive, sup, aux, theta, k, rounds, nupd = state
        live = jnp.any(alive, axis=1)
        cur = jnp.where(alive, sup, _fd_big(sup))
        k = jnp.maximum(k, jnp.min(cur, axis=1))
        S = alive & (sup <= k[:, None])
        # per live partition S is non-empty (k ≥ its min alive support):
        # every iteration is one real peel round of every live partition
        theta = jnp.where(S, k[:, None], theta)
        alive = alive & ~S
        loss, aux, nu = update(S, aux)
        return (alive, sup - loss, aux, theta, k,
                rounds + live.astype(jnp.int32), nupd + nu)

    # derive loop-constant inits from varying inputs (cf. _fd_while_device)
    zero_e = sup0 * 0
    zero_p = jnp.min(zero_e, axis=1)
    init = (mine, sup0, aux, zero_e, zero_p, zero_p, jnp.int32(0))
    _, _, _, theta, _, rounds, nupd = jax.lax.while_loop(cond, body, init)
    return theta, rounds, nupd


def _fd_while_fused(state0, round_fn):
    """The zero-per-round-dispatch FD driver: the whole cascade is one
    ``lax.while_loop`` whose body is ONE fused Pallas round
    (``kernels.fd_round`` — k-advance, frontier compaction and support
    update all in-kernel), so a round's jaxpr is a single ``pallas_call``
    with no segment-sum / argmin / compaction tail.

    ``state0`` is the loop-carried tuple with the alive mask (any dtype,
    nonzero = alive) at index 1; ``round_fn(*state) -> state`` must be
    the fused round.  Loop-invariant operands (slot layouts, pair lists)
    stay closed over inside ``round_fn`` — they never enter the carry.
    Semantics (k-advance, per-partition round counts, θ) are
    bit-identical to :func:`_fd_while_vmapped` / :func:`_fd_while_device`
    (golden- and property-locked in ``tests/test_fused_fd.py``)."""

    def cond(state):
        return jnp.any(state[1] != 0)

    def body(state):
        return round_fn(*state)

    return jax.lax.while_loop(cond, body, state0)


# =====================================================================
# Telemetry-ON twins of the FD cascade drivers (obs counter rings)
# =====================================================================
# Each ``*_rings`` function repeats its twin's loop algebra VERBATIM and
# additionally threads preallocated per-round counter rings through
# the carry — dying count, frontier size, k-advance (in the supports'
# dtype), update count (int32) — written at slot ``min(round, cap-1)`` (first cap-1 rounds
# plus the final round survive an overflow; the drain flags it
# ``truncated``).  They are separate functions, not a branch inside the
# twins, so the telemetry-OFF path traces the byte-identical jaxpr — a
# guarantee locked by ``tests/goldens/obs_jaxprs.json``.  Entity
# wrappers in ``core.peel`` expose them behind a static ``ring_cap``
# argument and drain the rings into the run's timeline collector.

def _fd_while_device_rings(mine: jax.Array, sup0: jax.Array, update, aux,
                           ring_cap: int):
    """:func:`_fd_while_device` + counter rings; returns
    ``(theta, rounds, nupd, (died, frontier, k, upd))`` with each ring
    shaped ``(ring_cap,)``."""
    cap = int(ring_cap)

    def cond(state):
        alive, *_ = state
        return jnp.any(alive)

    def body(state):
        alive, sup, aux, theta, k, rounds, nupd, rings = state
        died_r, fr_r, k_r, nu_r = rings
        cur = jnp.where(alive, sup, _fd_big(sup))
        k = jnp.maximum(k, jnp.min(cur))
        S = alive & (sup <= k)
        theta = jnp.where(S, k, theta)
        alive = alive & ~S
        loss, aux, nu = update(S, aux)
        slot = jnp.minimum(rounds, cap - 1)
        rings = (
            died_r.at[slot].set(
                jnp.sum(S.astype(jnp.int32)).astype(jnp.int32)),
            fr_r.at[slot].set(
                jnp.sum(alive.astype(jnp.int32)).astype(jnp.int32)),
            k_r.at[slot].set(k),
            nu_r.at[slot].set(jnp.asarray(nu).astype(jnp.int32)),
        )
        return (alive, sup - loss, aux, theta, k, rounds + 1, nupd + nu,
                rings)

    zero_e = sup0 * 0
    zero_s = jnp.min(zero_e)
    zring = jnp.zeros((cap,), jnp.int32)
    init = (mine, sup0, aux, zero_e, zero_s, zero_s, zero_s,
            (zring, zring, jnp.zeros((cap,), sup0.dtype), zring))
    out = jax.lax.while_loop(cond, body, init)
    return out[3], out[5], out[6], out[7]


def _fd_while_vmapped_rings(mine: jax.Array, sup0: jax.Array, update, aux,
                            ring_cap: int):
    """:func:`_fd_while_vmapped` + counter rings; returns
    ``(theta, rounds, nupd, (died, frontier, k, upd))`` where the first
    three rings are ``(ring_cap, B)`` and the update ring ``(ring_cap,)``
    (the engine's per-round update count is a phase-global scalar)."""
    cap = int(ring_cap)

    def cond(state):
        alive, *_ = state
        return jnp.any(alive)

    def body(state):
        alive, sup, aux, theta, k, rounds, nupd, it, rings = state
        died_r, fr_r, k_r, nu_r = rings
        live = jnp.any(alive, axis=1)
        cur = jnp.where(alive, sup, _fd_big(sup))
        k = jnp.maximum(k, jnp.min(cur, axis=1))
        S = alive & (sup <= k[:, None])
        theta = jnp.where(S, k[:, None], theta)
        alive = alive & ~S
        loss, aux, nu = update(S, aux)
        slot = jnp.minimum(it, cap - 1)
        rings = (
            died_r.at[slot].set(
                jnp.sum(S.astype(jnp.int32), axis=1).astype(jnp.int32)),
            fr_r.at[slot].set(
                jnp.sum(alive.astype(jnp.int32), axis=1).astype(jnp.int32)),
            k_r.at[slot].set(k),
            nu_r.at[slot].set(jnp.asarray(nu).astype(jnp.int32)),
        )
        return (alive, sup - loss, aux, theta, k,
                rounds + live.astype(jnp.int32), nupd + nu, it + 1, rings)

    zero_e = sup0 * 0
    zero_p = jnp.min(zero_e, axis=1)
    B = sup0.shape[0]
    zrow = jnp.zeros((cap, B), jnp.int32)
    init = (mine, sup0, aux, zero_e, zero_p, zero_p, jnp.int32(0),
            jnp.int32(0), (zrow, zrow, jnp.zeros((cap, B), sup0.dtype),
                           jnp.zeros((cap,), jnp.int32)))
    out = jax.lax.while_loop(cond, body, init)
    return out[3], out[5], out[6], out[8]


def _fd_while_fused_rings(state0, round_fn, ring_cap: int):
    """:func:`_fd_while_fused` + counter rings derived OUTSIDE the
    fused round (the Pallas kernel itself is untouched): died/frontier
    from the alive mask (state index 1, nonzero = alive) before/after
    the round, k from state index 3, and — when the state carries a
    per-partition update count at index 5 (the wing 8-tuple) — the ring
    stores its *cumulative* value per round (the drain converts to
    deltas via ``cumulative_updates=True``).  Returns
    ``(state, (died, frontier, k, upd_cum))``, rings ``(ring_cap, B)``.
    """
    cap = int(ring_cap)
    B = state0[1].shape[0]

    def cond(carry):
        state, _, _ = carry
        return jnp.any(state[1] != 0)

    def body(carry):
        state, it, rings = carry
        died_r, fr_r, k_r, nu_r = rings
        alive_before = jnp.sum((state[1] != 0).astype(jnp.int32), axis=1)
        new = round_fn(*state)
        alive_after = jnp.sum((new[1] != 0).astype(jnp.int32), axis=1)
        k_now = new[3][:, 0].astype(jnp.int32)
        nu_cum = (jnp.sum(new[5], axis=1).astype(jnp.int32)
                  if len(new) > 5 else jnp.zeros((B,), jnp.int32))
        slot = jnp.minimum(it, cap - 1)
        rings = (
            died_r.at[slot].set(alive_before - alive_after),
            fr_r.at[slot].set(alive_after),
            k_r.at[slot].set(k_now),
            nu_r.at[slot].set(nu_cum),
        )
        return (new, it + 1, rings)

    zrow = jnp.zeros((cap, B), jnp.int32)
    state, _, rings = jax.lax.while_loop(
        cond, body, (state0, jnp.int32(0), (zrow, zrow, zrow, zrow)))
    return state, rings
