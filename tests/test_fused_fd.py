"""Fused FD round kernel (``kernels/fd_round.py``) — the zero-per-round-
dispatch tentpole.

Three layers of lock:
  * kernel ↔ pure-jnp oracle (``kernels/ref.py``) parity in interpret
    mode, single-shot and iterated to the fixed point;
  * structural jaxpr assertions — the ops-layer round wrapper is exactly
    ONE ``pallas_call`` at top level, and the whole fused Phase 2 is ONE
    ``while`` whose body holds one ``pallas_call`` and no segment-sum /
    gather / argmin / compaction tail;
  * end-to-end bit-identity — every csr golden cell (device + vmapped)
    re-run with ``fused=True`` must match ``tests/goldens/
    peel_goldens.json`` field-for-field (θ, partitioning, round/update
    counts), plus a hypothesis property on random graphs.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ref as core_ref
from repro.core.graph import powerlaw_bipartite, random_bipartite
from repro.core.peel import (
    _fd_tip_fused_impl,
    _fd_wing_fused_impl,
    tip_decomposition,
    wing_decomposition,
)
from repro.kernels import ops, ref

GOLDENS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "goldens", "peel_goldens.json")

_BANNED = {"scatter", "scatter-add", "scatter_add", "gather", "argmin",
           "reduce_min", "cumsum", "sort", "segment_sum"}


# ---------------------------------------------------------------------
# packed-state builders (the same layouts the peel drivers feed)
# ---------------------------------------------------------------------
def _wing_state(seed=0, n_u=30, n_v=24, m=140, P=4):
    from repro.core import csr
    from repro.core.distributed import pack_fd_partitions_csr

    g = random_bipartite(n_u, n_v, m, seed=seed)
    wed = csr.build_wedges(g)
    res = wing_decomposition(g, P=P, engine="csr")
    n_parts = int(res.part.max()) + 1
    p = pack_fd_partitions_csr(
        wed, res.part, res.support_init, n_parts, bucket=True, slots=True)
    R, _ = p["slot_sizes"]
    W_rows = np.zeros((n_parts, R), np.int32)
    w = min(R, p["W0"].shape[1])
    W_rows[:, :w] = p["W0"][:, :w]
    z = jnp.asarray(p["sup0"]).astype(jnp.int32) * 0
    z1 = z[:, :1]
    state = (jnp.asarray(p["sup0"]).astype(jnp.int32),
             jnp.asarray(p["mine"]).astype(jnp.int32), z, z1, z1, z1,
             jnp.asarray(p["slot_valid"]).astype(jnp.int32),
             jnp.asarray(W_rows).astype(jnp.float32))
    statics = (jnp.asarray(p["slot_e1"]), jnp.asarray(p["slot_e2"]))
    return state, statics, p


def _tip_state(seed=0, n_u=30, n_v=24, m=140, P=4):
    from repro.core import csr
    from repro.core.distributed import pack_fd_partitions_tip_csr

    g = random_bipartite(n_u, n_v, m, seed=seed)
    wed = csr.build_wedges(g)
    res = tip_decomposition(g, side="u", P=P, engine="csr")
    n_parts = int(res.part.max()) + 1
    p = pack_fd_partitions_tip_csr(
        wed, wed.pair_butterflies0(), res.part, res.support_init,
        n_parts, bucket=True, stacked=True)
    z = jnp.asarray(p["sup0"]).astype(jnp.int32) * 0
    z1 = z[:, :1]
    state = (jnp.asarray(p["sup0"]).astype(jnp.int32),
             jnp.asarray(p["mine"]).astype(jnp.int32), z, z1, z1)
    statics = (jnp.asarray(p["st_pa"]), jnp.asarray(p["st_pb"]),
               jnp.asarray(p["st_bf"]))
    return state, statics, p


# ---------------------------------------------------------------------
# kernel ↔ oracle parity (interpret mode, the KERNELS.md recipe)
# ---------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fd_round_wing_kernel_matches_ref(seed):
    state, statics, _ = _wing_state(seed=seed)
    # iterate to the fixed point: every round's full 8-tuple must agree
    for _ in range(40):
        got = ops.fd_round_wing(*state, *statics)
        want = ref.fd_round_wing_ref(*state, *statics)
        for i, (a, b) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"output {i}")
        state = got
        if not np.asarray(state[1]).any():
            break
    assert not np.asarray(state[1]).any(), "cascade did not converge"


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fd_round_tip_kernel_matches_ref(seed):
    state, statics, _ = _tip_state(seed=seed)
    for _ in range(40):
        got = ops.fd_round_tip(*state, *statics)
        want = ref.fd_round_tip_ref(*state, *statics)
        for i, (a, b) in enumerate(zip(got, want)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"output {i}")
        state = got
        if not np.asarray(state[1]).any():
            break
    assert not np.asarray(state[1]).any(), "cascade did not converge"


# ---------------------------------------------------------------------
# structural jaxpr locks
# ---------------------------------------------------------------------
def test_wing_round_wrapper_is_single_pallas_call():
    """The ops-layer round body must trace to exactly ONE top-level
    pallas_call — nothing before it, nothing after it (this is why the
    wrapper is deliberately unjitted)."""
    state, statics, _ = _wing_state()
    jx = jax.make_jaxpr(ops.fd_round_wing)(*state, *statics)
    prims = [e.primitive.name for e in jx.jaxpr.eqns]
    assert prims == ["pallas_call"], prims


def test_tip_round_wrapper_is_single_pallas_call():
    state, statics, _ = _tip_state()
    jx = jax.make_jaxpr(ops.fd_round_tip)(*state, *statics)
    prims = [e.primitive.name for e in jx.jaxpr.eqns]
    assert prims == ["pallas_call"], prims


def _assert_fused_phase_structure(jx):
    whiles = [e for e in jx.jaxpr.eqns if e.primitive.name == "while"]
    assert len(whiles) == 1, [e.primitive.name for e in jx.jaxpr.eqns]
    body = [e.primitive.name
            for e in whiles[0].params["body_jaxpr"].jaxpr.eqns]
    assert body.count("pallas_call") == 1, body
    assert not _BANNED & set(body), body


def test_fused_wing_phase_is_one_while_one_pallas_call():
    """Whole fused wing Phase 2: ONE while_loop whose body is ONE
    pallas_call — the zero-per-round-dispatch claim, stated on the
    jaxpr."""
    state, statics, p = _wing_state()
    _assert_fused_phase_structure(jax.make_jaxpr(_fd_wing_fused_impl)(
        statics[0], statics[1], jnp.asarray(p["slot_valid"]),
        state[7].astype(jnp.int32), jnp.asarray(p["mine"]),
        jnp.asarray(p["sup0"])))


def test_fused_tip_phase_is_one_while_one_pallas_call():
    state, statics, p = _tip_state()
    _assert_fused_phase_structure(jax.make_jaxpr(_fd_tip_fused_impl)(
        *statics, jnp.asarray(p["mine"]), jnp.asarray(p["sup0"])))


# ---------------------------------------------------------------------
# end-to-end bit-identity vs the pre-refactor goldens
# ---------------------------------------------------------------------
_GRAPHS = {
    "rb30": lambda: random_bipartite(30, 24, 140, seed=0),
    "rb25": lambda: random_bipartite(25, 20, 100, seed=1),
    "pl80": lambda: powerlaw_bipartite(80, 40, 350, seed=2),
    "pl60": lambda: powerlaw_bipartite(60, 50, 300, seed=3),
}

_FIELDS = ("theta", "part", "ranges", "support_init", "rho_cd",
           "rho_fd_total", "rho_fd_max", "updates", "recounts",
           "p_effective")


def _snapshot(res) -> dict:
    s = res.stats
    return dict(
        theta=np.asarray(res.theta).tolist(),
        part=np.asarray(res.part).tolist(),
        ranges=np.asarray(res.ranges).tolist(),
        support_init=np.asarray(res.support_init).tolist(),
        rho_cd=s.rho_cd, rho_fd_total=s.rho_fd_total,
        rho_fd_max=s.rho_fd_max, updates=s.updates,
        recounts=s.recounts, p_effective=s.p_effective,
    )


@pytest.fixture(scope="module")
def goldens():
    with open(GOLDENS) as f:
        return json.load(f)


@pytest.mark.parametrize("gname", sorted(_GRAPHS))
def test_fused_wing_matches_csr_goldens(goldens, gname):
    """fused=True against the SAME goldens the unfused drivers lock to —
    a mismatch means the fusion changed peeling semantics."""
    g = _GRAPHS[gname]()
    cases = [k for k in goldens if k.startswith(f"wing.{gname}.")
             and k.split(".")[3] == "csr"
             and k.split(".")[4] in ("device", "vmapped")]
    assert cases, "golden file lost its csr wing cases"
    for key in cases:
        _, _, Ps, engine, fd = key.split(".")
        res = wing_decomposition(
            g, P=int(Ps[1:]), engine=engine, fd_driver=fd, fused=True)
        got = _snapshot(res)
        for f in _FIELDS:
            assert got[f] == goldens[key][f], (key, f)


@pytest.mark.parametrize("gname", sorted(_GRAPHS))
def test_fused_tip_matches_csr_goldens(goldens, gname):
    g = _GRAPHS[gname]()
    cases = [k for k in goldens if k.startswith(f"tip.{gname}.")
             and k.split(".")[4] == "csr"
             and k.split(".")[5] in ("device", "vmapped")]
    assert cases, "golden file lost its csr tip cases"
    for key in cases:
        _, _, Ps, side, engine, fd = key.split(".")
        res = tip_decomposition(
            g, side=side, P=int(Ps[1:]), engine=engine, fd_driver=fd,
            fused=True)
        got = _snapshot(res)
        for f in _FIELDS:
            assert got[f] == goldens[key][f], (key, f)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 5))
def test_fused_unfused_parity_property(seed, P):
    """Property: fused and unfused drivers agree bit-for-bit on random
    graphs — θ, partitioning AND round/update counts — and match the
    BUP oracle."""
    g = random_bipartite(18, 14, 60, seed=seed)

    base = wing_decomposition(g, P=P, engine="csr")
    assert np.array_equal(base.theta, core_ref.bup_wing_ref(g))
    for fd in ("device", "vmapped"):
        other = wing_decomposition(g, P=P, engine="csr", fd_driver=fd,
                                   fused=True)
        assert np.array_equal(other.theta, base.theta), fd
        assert np.array_equal(other.part, base.part), fd
        assert other.stats.rho_fd_total == base.stats.rho_fd_total, fd
        assert other.stats.rho_fd_max == base.stats.rho_fd_max, fd
        assert other.stats.updates == base.stats.updates, fd

    tbase = tip_decomposition(g, side="u", P=P, engine="csr")
    assert np.array_equal(tbase.theta, core_ref.bup_tip_ref(g, "u"))
    for fd in ("device", "vmapped"):
        other = tip_decomposition(g, side="u", P=P, engine="csr",
                                  fd_driver=fd, fused=True)
        assert np.array_equal(other.theta, tbase.theta), fd
        assert np.array_equal(other.part, tbase.part), fd
        assert other.stats.rho_fd_total == tbase.stats.rho_fd_total, fd
        assert other.stats.rho_fd_max == tbase.stats.rho_fd_max, fd


def test_fused_rejects_unsupported_combinations():
    g = random_bipartite(10, 8, 24, seed=0)
    with pytest.raises(ValueError):
        wing_decomposition(g, engine="beindex", fused=True)
    with pytest.raises(ValueError):
        wing_decomposition(g, engine="csr", fd_driver="host", fused=True)
    with pytest.raises(ValueError):
        tip_decomposition(g, engine="dense", fused=True)
    with pytest.raises(ValueError):
        tip_decomposition(g, engine="csr", fd_driver="host", fused=True)


def test_obs_off_fd_jaxprs_byte_identical(obs_golden):
    """Zero-overhead-off: with telemetry disabled (the default), the
    fused and vmapped FD programs re-derived from the instrumented tree
    are byte-identical to the pre-instrumentation goldens
    (``tests/goldens/obs_jaxprs.json``).  The counter rings the obs
    layer threads through the FD loop carries live in separate
    ``*_rings`` jit twins — the default entries may not trace a single
    extra op."""
    from repro import obs

    rec, golden = obs_golden
    assert not obs.enabled()
    for name in ("fused_wing", "fused_tip", "vmapped_wing",
                 "vmapped_tip"):
        assert rec.CASES[name]() == golden[name], \
            f"{name}: default-path jaxpr drifted from the telemetry-off " \
            f"golden (re-record ONLY for intentional kernel changes)"
