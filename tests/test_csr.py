"""CSR wedge-list engine ≡ BUP oracle, plus wedge-count kernel parity."""
import os

import jax
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import csr, ref
from repro.core.graph import BipartiteGraph, powerlaw_bipartite, random_bipartite
from repro.core.peel import tip_decomposition, wing_decomposition
from repro.kernels import ops
from repro.kernels import ref as kref


def graphs(max_u=16, max_v=14, max_m=50):
    return st.builds(
        lambda nu, nv, m, seed: random_bipartite(nu, nv, m, seed=seed),
        st.integers(2, max_u), st.integers(2, max_v),
        st.integers(0, max_m), st.integers(0, 10_000),
    )


# ------------------------------------------------------------- counting
@pytest.mark.parametrize("seed", range(6))
def test_csr_counts_match_oracle(seed):
    g = random_bipartite(30, 24, 140, seed=seed)
    w = csr.build_wedges(g)
    bu, _ = ref.vertex_butterflies_ref(g)
    assert np.array_equal(csr.vertex_butterflies_csr(w), bu)
    got_e = np.asarray(csr.edge_butterflies_csr(w)).astype(np.int64)
    assert np.array_equal(got_e, ref.edge_butterflies_ref(g))
    assert np.array_equal(csr.edge_butterflies0(w), got_e)
    assert csr.total_butterflies_csr(w) == ref.butterfly_count_total(g)
    wu, wv = csr.wedge_workload(g)
    ru, rv = ref.wedge_count_ref(g)
    assert np.array_equal(wu, ru) and np.array_equal(wv, rv)


@pytest.mark.parametrize("seed", range(4))
def test_csr_masked_recount_matches_subgraph_oracle(seed):
    g = random_bipartite(24, 20, 110, seed=seed)
    w = csr.build_wedges(g)
    rng = np.random.default_rng(seed)
    alive = rng.random(g.m) > 0.35
    sub = BipartiteGraph.from_edges(g.n_u, g.n_v, g.edges[alive])
    got = np.asarray(csr.edge_butterflies_csr(w, jnp.asarray(alive)))[alive]
    assert np.array_equal(got.astype(np.int64), ref.edge_butterflies_ref(sub))


@pytest.mark.parametrize("seed", range(4))
def test_csr_incremental_update_equals_recount(seed):
    """One wing_update_csr round == recount on the shrunken subgraph."""
    g = random_bipartite(22, 18, 100, seed=seed)
    w = csr.build_wedges(g)
    rng = np.random.default_rng(seed + 100)
    peeled = rng.random(g.m) < 0.3
    alive = ~peeled
    we1, we2, wp = map(jnp.asarray, (w.wedge_e1, w.wedge_e2, w.wedge_pair))
    _, _, sup, _ = csr.wing_update_csr(
        jnp.asarray(peeled),
        jnp.ones((w.n_wedges,), bool),
        csr.pair_wedge_counts(w),
        csr.edge_butterflies_csr(w),
        we1, we2, wp, w.n_pairs, g.m,
    )
    want = np.asarray(csr.edge_butterflies_csr(w, jnp.asarray(alive)))
    assert np.array_equal(np.asarray(sup)[alive], want[alive])


def test_empty_and_tiny_graphs():
    for edges in ([], [[0, 0]], [[0, 0], [1, 1]]):
        g = BipartiteGraph.from_edges(2, 2, np.asarray(edges, np.int32).reshape(-1, 2))
        w = csr.build_wedges(g)
        assert csr.total_butterflies_csr(w) == ref.butterfly_count_total(g)
        res = wing_decomposition(g, P=2, engine="csr")
        assert np.array_equal(res.theta, ref.bup_wing_ref(g))


# ------------------------------------------------------------- peeling
@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("P", [1, 4])
def test_tip_csr_matches_bup(seed, P):
    g = random_bipartite(16, 13, 48, seed=seed)
    for side in ("u", "v"):
        want = ref.bup_tip_ref(g, side)
        got = tip_decomposition(g, side=side, P=P, engine="csr").theta
        assert np.array_equal(got, want)


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("P", [1, 4])
def test_wing_csr_matches_bup(seed, P):
    g = random_bipartite(16, 13, 48, seed=seed)
    want = ref.bup_wing_ref(g)
    got = wing_decomposition(g, P=P, engine="csr").theta
    assert np.array_equal(got, want)


def test_wing_csr_matches_beindex_on_skewed_graph():
    g = powerlaw_bipartite(80, 50, 420, seed=11)
    r_csr = wing_decomposition(g, P=8, engine="csr")
    r_be = wing_decomposition(g, P=8, engine="beindex")
    assert np.array_equal(r_csr.theta, r_be.theta)
    assert r_csr.stats.rho_cd > 0 and r_csr.stats.updates > 0


@settings(max_examples=20, deadline=None)
@given(graphs(), st.integers(1, 5), st.sampled_from(["u", "v"]))
def test_tip_csr_matches_bup_property(g, P, side):
    want = ref.bup_tip_ref(g, side)
    got = tip_decomposition(g, side=side, P=P, engine="csr").theta
    assert np.array_equal(got, want)


@settings(max_examples=15, deadline=None)
@given(graphs(), st.integers(1, 4))
def test_wing_csr_matches_bup_property(g, P):
    want = ref.bup_wing_ref(g)
    got = wing_decomposition(g, P=P, engine="csr").theta
    assert np.array_equal(got, want)


@settings(max_examples=15, deadline=None)
@given(graphs(max_u=12, max_v=10, max_m=40), st.integers(1, 4))
def test_wing_engines_and_fd_drivers_agree_property(g, P):
    """csr (device while_loop FD), csr (vmapped single-dispatch FD), csr
    (host-loop FD) and dense must all produce identical theta — and the
    three FD drivers identical round / update counts (same cascade,
    different residency)."""
    dev = wing_decomposition(g, P=P, engine="csr", fd_driver="device")
    vm = wing_decomposition(g, P=P, engine="csr", fd_driver="vmapped")
    host = wing_decomposition(g, P=P, engine="csr", fd_driver="host")
    dense = wing_decomposition(g, P=P, engine="dense")
    assert np.array_equal(dev.theta, host.theta)
    assert np.array_equal(dev.theta, vm.theta)
    assert np.array_equal(dev.theta, dense.theta)
    assert dev.stats.rho_fd_total == host.stats.rho_fd_total
    assert dev.stats.rho_fd_total == vm.stats.rho_fd_total
    assert dev.stats.rho_fd_max == vm.stats.rho_fd_max
    assert dev.stats.updates == host.stats.updates
    assert dev.stats.updates == vm.stats.updates
    assert dev.stats.fd_driver == "device"
    assert vm.stats.fd_driver == "vmapped"
    assert host.stats.fd_driver == "host"


@settings(max_examples=15, deadline=None)
@given(graphs(max_u=12, max_v=10, max_m=40), st.integers(1, 4),
       st.sampled_from(["u", "v"]))
def test_tip_engines_and_fd_drivers_agree_property(g, P, side):
    dev = tip_decomposition(g, side=side, P=P, engine="csr",
                            fd_driver="device")
    host = tip_decomposition(g, side=side, P=P, engine="csr",
                             fd_driver="host")
    dense = tip_decomposition(g, side=side, P=P, engine="dense")
    assert np.array_equal(dev.theta, host.theta)
    assert np.array_equal(dev.theta, dense.theta)
    assert dev.stats.rho_fd_total == host.stats.rho_fd_total


@pytest.mark.parametrize("decompose", [wing_decomposition, tip_decomposition])
def test_removed_fused_option_is_a_type_error(decompose):
    """One FD path per driver: the fused option is gone, not ignored."""
    g = random_bipartite(12, 10, 40, seed=0)
    with pytest.raises(TypeError, match="fused"):
        decompose(g, engine="csr", fused=True)


# -------------------------------------------------------- scale / guard
def test_dense_engine_guarded_csr_peels_50k_graph():
    """The acceptance graph: 50k×50k, avg degree 8.

    The dense engine must refuse it up front (its adjacency alone is
    10 GB); the csr engine must peel it."""
    g = random_bipartite(50_000, 50_000, 400_000, seed=0)
    with pytest.raises(MemoryError):
        tip_decomposition(g, P=4, engine="dense")
    with pytest.raises(MemoryError):
        wing_decomposition(g, P=4, engine="dense")
    res = tip_decomposition(g, P=4, engine="csr")
    assert res.theta.shape == (g.n_u,)
    assert res.stats.rho_cd > 0
    resw = wing_decomposition(g, P=4, engine="csr")
    assert resw.theta.shape == (g.m,)


def test_dense_guard_env_override(monkeypatch):
    g = random_bipartite(40, 30, 150, seed=1)
    monkeypatch.setitem(os.environ, "REPRO_DENSE_MAX_ELEMS", "100")
    with pytest.raises(MemoryError):
        tip_decomposition(g, P=2, engine="dense")


# ------------------------------------------------------------- kernels
@pytest.mark.parametrize("shape", [(7, 30), (64, 128), (130, 260)])
def test_wedge_count_kernel_matches_ref(shape):
    rng = np.random.default_rng(shape[0])
    slots = jnp.asarray(rng.random(shape) > 0.4)
    W, bf = ops.pair_wedge_counts(slots, interpret=True)
    Wr, bfr = kref.pair_wedge_counts_ref(slots)
    np.testing.assert_array_equal(np.asarray(W), np.asarray(Wr))
    np.testing.assert_array_equal(np.asarray(bf), np.asarray(bfr))


@pytest.mark.parametrize("seed", range(3))
def test_wedge_count_kernel_matches_segment_sum(seed):
    g = random_bipartite(60, 45, 350, seed=seed)
    w = csr.build_wedges(g)
    rng = np.random.default_rng(seed)
    for alive in (None, jnp.asarray(rng.random(g.m) > 0.25)):
        Wseg = np.asarray(csr.pair_wedge_counts(w, alive))
        Wpal = np.asarray(
            csr.pair_wedge_counts(w, alive, use_pallas=True, interpret=True)
        )
        assert np.array_equal(Wseg, Wpal)
        s_seg = np.asarray(csr.edge_butterflies_csr(w, alive))
        s_pal = np.asarray(
            csr.edge_butterflies_csr(w, alive, use_pallas=True, interpret=True)
        )
        assert np.array_equal(s_seg, s_pal)


@pytest.mark.parametrize("shape", [(7, 30), (64, 128), (130, 260)])
def test_support_update_kernel_matches_ref(shape):
    """Interpret-mode parity: blocked support-update kernel vs oracle."""
    rng = np.random.default_rng(shape[1])
    alive = rng.random(shape) > 0.3
    pe1 = rng.random(shape) > 0.6
    pe2 = rng.random(shape) > 0.6
    W = rng.integers(0, 40, shape[0])
    args = (jnp.asarray(pe1), jnp.asarray(pe2), jnp.asarray(alive),
            jnp.asarray(W.astype(np.float32)))
    c1, c2, c = ops.support_update(*args, interpret=True)
    r1, r2, rc = kref.support_update_ref(*args)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(r1))
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))


@pytest.mark.parametrize("seed", range(3))
def test_wing_update_slots_matches_segment_sum(seed):
    """One Pallas slot-layout update round == one flat wing_update_csr
    round (support, W, alive, and update counts all identical)."""
    g = random_bipartite(22, 18, 100, seed=seed)
    w = csr.build_wedges(g)
    rng = np.random.default_rng(seed + 500)
    peeled = rng.random(g.m) < 0.3
    we1, we2, wp = map(jnp.asarray, (w.wedge_e1, w.wedge_e2, w.wedge_pair))
    W0 = csr.pair_wedge_counts(w)
    sup0 = csr.edge_butterflies_csr(w)
    a_f, W_f, s_f, n_f = csr.wing_update_csr(
        jnp.asarray(peeled), jnp.ones((w.n_wedges,), bool), W0, sup0,
        we1, we2, wp, w.n_pairs, g.m)
    slots = csr.pack_update_slots(w)
    a_s, W_s, s_s, n_s = csr.wing_update_slots(
        jnp.asarray(peeled), jnp.asarray(slots["valid"]), W0, sup0,
        jnp.asarray(slots["e1"]), jnp.asarray(slots["e2"]),
        w.n_pairs, g.m, interpret=True)
    assert np.array_equal(np.asarray(W_f), np.asarray(W_s))
    assert np.array_equal(np.asarray(s_f), np.asarray(s_s))
    assert int(n_f) == int(n_s)
    packed = csr.pack_wedge_slots(w)
    flat_alive = np.zeros(w.n_wedges, bool)
    flat_alive[np.maximum(packed.idx, 0)[packed.valid]] = np.asarray(
        a_s)[packed.valid]
    assert np.array_equal(flat_alive, np.asarray(a_f))


def test_wing_csr_pallas_cd_matches():
    """Full decomposition with the Pallas CD update path ≡ segment_sum."""
    g = powerlaw_bipartite(60, 40, 260, seed=3)
    r0 = wing_decomposition(g, P=6, engine="csr")
    r1 = wing_decomposition(g, P=6, engine="csr", use_pallas=True)
    assert np.array_equal(r0.theta, r1.theta)
    assert r0.stats.rho_cd == r1.stats.rho_cd
    assert r0.stats.updates == r1.stats.updates


def test_fd_device_driver_is_single_while_loop():
    """The acceptance property: one partition's csr FD cascade lowers to
    exactly one while op — zero host round-trips inside a partition."""
    from repro.core.peel import _fd_tip_device, _fd_wing_device

    g = random_bipartite(16, 13, 48, seed=0)
    w = csr.build_wedges(g)
    mine = jnp.ones((g.n_u,), bool)
    sup0 = jnp.asarray(csr.vertex_butterflies_csr(w).astype(np.int32))
    jaxpr = jax.make_jaxpr(
        lambda *a: _fd_tip_device(*a, n=g.n_u)
    )(mine, sup0, jnp.asarray(w.pair_a), jnp.asarray(w.pair_b),
      jnp.asarray(w.pair_butterflies0().astype(np.int32)))
    assert str(jaxpr).count("while[") == 1

    mine_e = jnp.ones((g.m,), bool)
    sup_e = jnp.asarray(csr.edge_butterflies0(w).astype(np.int32))
    jaxpr_w = jax.make_jaxpr(
        lambda *a: _fd_wing_device(*a, n_pairs=w.n_pairs, m=g.m)
    )(mine_e, sup_e, jnp.ones((w.n_wedges,), bool),
      jnp.asarray(w.W0.astype(np.int32)),
      jnp.asarray(w.wedge_e1), jnp.asarray(w.wedge_e2),
      jnp.asarray(w.wedge_pair))
    assert str(jaxpr_w).count("while[") == 1


def test_vmapped_fd_single_while_zero_collectives():
    """The acceptance property of the single-dispatch FD: the FULL csr
    Phase 2 — every partition — lowers to exactly ONE while op with zero
    collectives, for both the segment-sum and the in-loop Pallas body
    (not one while per partition: one, total)."""
    from repro.core import distributed as D
    from repro.core.peel import _fd_wing_vmapped, _fd_wing_vmapped_pallas

    g = powerlaw_bipartite(60, 40, 260, seed=3)
    wed = csr.build_wedges(g)
    res = wing_decomposition(g, P=6, engine="csr")
    assert res.stats.p_effective > 1  # a real multi-partition cascade
    packed = D.pack_fd_partitions_csr(
        wed, res.part, res.support_init, res.stats.p_effective,
        bucket=True, flat=True, slots=True,
    )
    args = tuple(jnp.asarray(packed[k]) for k in
                 ("flat_we1", "flat_we2", "flat_wp", "flat_alive0",
                  "flat_W0", "mine", "sup0"))
    n_pairs = int(packed["flat_W0"].shape[0])
    jaxpr = str(jax.make_jaxpr(
        lambda *a: _fd_wing_vmapped(*a, n_pairs=n_pairs))(*args))
    assert jaxpr.count("while[") == 1
    for coll in ("psum", "all_reduce", "all_gather", "ppermute",
                 "all_to_all"):
        assert coll not in jaxpr, coll

    R, _ = packed["slot_sizes"]
    W_rows = np.zeros((packed["W0"].shape[0], R), np.int32)
    w = min(R, packed["W0"].shape[1])
    W_rows[:, :w] = packed["W0"][:, :w]
    argsp = (jnp.asarray(packed["slot_e1"]), jnp.asarray(packed["slot_e2"]),
             jnp.asarray(packed["slot_valid"]), jnp.asarray(W_rows),
             jnp.asarray(packed["mine"]), jnp.asarray(packed["sup0"]))
    jaxpr_p = str(jax.make_jaxpr(
        lambda *a: _fd_wing_vmapped_pallas(*a, interpret=True))(*argsp))
    assert jaxpr_p.count("while[") == 1
    for coll in ("psum", "all_reduce", "all_gather", "ppermute",
                 "all_to_all"):
        assert coll not in jaxpr_p, coll


@pytest.mark.parametrize("seed", range(3))
def test_wing_fd_vmapped_pallas_matches(seed):
    """vmapped FD with the in-loop Pallas support_update kernel ≡ the
    segment-sum body AND the per-partition driver: bit-identical θ,
    identical round/update counts (interpret-mode parity)."""
    g = powerlaw_bipartite(40, 30, 180, seed=seed)
    dev = wing_decomposition(g, P=4, engine="csr", fd_driver="device")
    vm = wing_decomposition(g, P=4, engine="csr", fd_driver="vmapped")
    vmp = wing_decomposition(g, P=4, engine="csr", fd_driver="vmapped",
                             use_pallas=True)
    assert np.array_equal(dev.theta, vm.theta)
    assert np.array_equal(dev.theta, vmp.theta)
    assert dev.stats.rho_fd_total == vm.stats.rho_fd_total \
        == vmp.stats.rho_fd_total
    assert dev.stats.rho_fd_max == vm.stats.rho_fd_max \
        == vmp.stats.rho_fd_max
    assert dev.stats.updates == vm.stats.updates == vmp.stats.updates


@settings(max_examples=10, deadline=None)
@given(graphs(max_u=12, max_v=10, max_m=40), st.integers(1, 4),
       st.sampled_from(["u", "v"]))
def test_tip_fd_vmapped_matches_property(g, P, side):
    dev = tip_decomposition(g, side=side, P=P, engine="csr",
                            fd_driver="device")
    vm = tip_decomposition(g, side=side, P=P, engine="csr",
                           fd_driver="vmapped")
    assert np.array_equal(dev.theta, vm.theta)
    assert dev.stats.rho_fd_total == vm.stats.rho_fd_total
    assert dev.stats.rho_fd_max == vm.stats.rho_fd_max


def test_vmapped_fd_mixed_shape_buckets():
    """Partitions whose individual sizes straddle different quarter-pow2
    buckets must still land in ONE stacked layout and one while_loop —
    and peel exactly.  A dense blob + a sparse tail forces a large and a
    small partition."""
    from repro.core import distributed as D
    from repro.core.peel import _bucket_pad

    rng = np.random.default_rng(7)
    # dense 8×8 complete blob (huge uniform supports) + a moderate
    # 30×20 block at 0.3 density on a DISJOINT V block: CD puts the
    # moderate block in partition 0 and the blob in partition 1, with
    # wedge-list sizes in different quarter-pow2 buckets
    blob = [(u, v) for u in range(8) for v in range(8)]
    mid = [(8 + u, 8 + v) for u in range(30) for v in range(20)
           if rng.random() < 0.3]
    edges = np.asarray(blob + mid, dtype=np.int32)
    g = BipartiteGraph.from_edges(38, 28, edges)
    res = wing_decomposition(g, P=4, engine="csr")
    n_parts = res.stats.p_effective
    assert n_parts > 1
    wed = csr.build_wedges(g)
    # per-partition touching-wedge list sizes must fall in distinct
    # quarter-pow2 buckets (the per-partition launcher would compile one
    # while_loop per bucket; the vmapped driver still gets ONE layout)
    pe1 = res.part[wed.wedge_e1]
    pe2 = res.part[wed.wedge_e2]
    pmin = np.minimum(pe1, pe2)
    sizes = [int(((pe1 >= i) & (pe2 >= i) & (pmin == i)).sum())
             for i in range(n_parts)]
    buckets = {_bucket_pad(s) for s in sizes}
    assert len(buckets) > 1, (sizes, buckets)

    packed = D.pack_fd_partitions_csr(
        wed, res.part, res.support_init, n_parts, bucket=True, flat=True)
    # one stacked layout: the rectangular stack pads every partition to
    # the SAME bucketed slot count; the flat concat holds all real
    # wedges in one bucketed run
    assert packed["we1"].shape[1] == _bucket_pad(max(sizes))
    assert packed["flat_we1"].shape[0] == _bucket_pad(sum(sizes))
    assert int(packed["flat_alive0"].sum()) == sum(sizes)
    for drv in ("vmapped",):
        r = wing_decomposition(g, P=4, engine="csr", fd_driver=drv)
        assert np.array_equal(r.theta, res.theta)
        assert r.stats.rho_fd_total == res.stats.rho_fd_total
    rp = wing_decomposition(g, P=4, engine="csr", fd_driver="vmapped",
                            use_pallas=True)
    assert np.array_equal(rp.theta, res.theta)


def test_peel_stats_per_engine_rho():
    """sync_reduction / as_dict must reflect the engine that actually
    ran — csr and dense report their own rho, tagged with the engine."""
    g = random_bipartite(20, 16, 70, seed=2)
    rc = wing_decomposition(g, P=4, engine="csr")
    rd = wing_decomposition(g, P=4, engine="dense")
    assert rc.stats.engine == "csr" and rd.stats.engine == "dense"
    dc = rc.stats.as_dict()
    assert dc["rho"] == rc.stats.rho_cd
    assert dc["sync_reduction"] == round(
        rc.stats.rho_fd_total / max(rc.stats.rho_cd, 1), 3)
    assert dc["fd_driver"] == "device"


def test_pad_segments_roundtrip():
    ids = np.asarray([0, 0, 2, 2, 2, 4], np.int32)
    p = csr.pad_segments(ids, 5)
    assert p.width % 128 == 0 and p.n_rows_pad % 8 == 0
    counts = p.valid.sum(axis=1)
    assert list(counts[:5]) == [2, 0, 3, 0, 1]
    # every original item appears exactly once
    got = np.sort(p.idx[p.valid])
    assert np.array_equal(got, np.arange(ids.size))
