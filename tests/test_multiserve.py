"""Multi-tenant serving: pooled-dispatch oracle parity, the one-compile-
per-bucket / zero-retrace invariants, LRU + pinned/queued eviction
semantics, and artifact format-version compatibility."""
import os

import numpy as np
import pytest

from repro.core.graph import powerlaw_bipartite
from repro.core.peel import wing_decomposition
from repro.hierarchy import (
    FORMAT_VERSION,
    ForestPool,
    HierarchyService,
    MTQuery,
    MultiTenantService,
    PoolFull,
    build_hierarchy,
    load_hierarchy,
    pack_forest,
    save_hierarchy,
)
from repro.hierarchy import multiserve
from repro.hierarchy.serve import OPS


# ------------------------------------------------------------------ helpers
def _hier(nu=40, nv=28, m=120, seed=0):
    g = powerlaw_bipartite(nu, nv, m, seed=seed)
    return build_hierarchy(g, wing_decomposition(g, P=4, engine="csr"))


@pytest.fixture(scope="module")
def tenant_dir(tmp_path_factory):
    """Six artifacts over two shape buckets: big0..big3 (40x28/120,
    one bucket) and small0..small1 (12x8/24, another)."""
    d = tmp_path_factory.mktemp("tenants")
    for i in range(4):
        save_hierarchy(str(d / f"big{i}.npz"), _hier(seed=i))
    for i in range(2):
        save_hierarchy(str(d / f"small{i}.npz"),
                       _hier(nu=12, nv=8, m=24, seed=10 + i))
    return str(d)


def _workload(pool, tenants, n, seed=0):
    rng = np.random.default_rng(seed)
    t_col = [tenants[i % len(tenants)] for i in range(n)]
    ops = rng.integers(0, 5, n).astype(np.int32)
    a = np.zeros(n, np.int32)
    b = np.zeros(n, np.int32)
    for i, t in enumerate(t_col):
        m = pool.meta[t]
        lim = m.n_nodes if ops[i] == OPS["subtree_size"] else m.n_entities
        a[i] = rng.integers(0, lim)
        b[i] = rng.integers(0, m.n_entities)
    return t_col, ops, a, b


def _oracle_answers(artifact_dir, tenants, ops, a, b):
    """Per-tenant HierarchyService answers, slot by slot."""
    svcs = {}
    out = np.zeros(len(tenants), np.int32)
    for i, t in enumerate(tenants):
        if t not in svcs:
            h = load_hierarchy(os.path.join(artifact_dir, f"{t}.npz"))
            svcs[t] = HierarchyService(h, batch=8)
        out[i] = svcs[t].query_batch(
            ops[i:i + 1], a[i:i + 1], b[i:i + 1])[0]
    return out


# ------------------------------------------------------------ oracle parity
def test_mixed_tenant_batch_matches_per_tenant_service(tenant_dir):
    """The tentpole claim: slot-batched pooled dispatch is bit-identical
    to running each query through its own single-tenant service."""
    pool = ForestPool(slots=8, artifact_dir=tenant_dir)
    svc = MultiTenantService(pool, batch=64)
    active = ["big0", "big1", "big2", "small0", "small1"]
    tenants, ops, a, b = _workload_all(pool, active)
    got = svc.query_batch(tenants, ops, a, b)
    want = _oracle_answers(tenant_dir, tenants, ops, a, b)
    np.testing.assert_array_equal(got, want)


def _workload_all(pool, active, n=400, seed=1):
    for t in active:
        pool.ensure(t)
    return _workload(pool, active, n, seed=seed)


def test_submit_run_roundtrip(tenant_dir):
    pool = ForestPool(slots=8, artifact_dir=tenant_dir)
    svc = MultiTenantService(pool, batch=32)
    h = load_hierarchy(os.path.join(tenant_dir, "big0.npz"))
    oracle = HierarchyService(h, batch=8)
    svc.submit(MTQuery(uid=7, tenant="big0", op="max_k", a=3))
    svc.submit(MTQuery(uid=1, tenant="big0", op="lca_level", a=1, b=5))
    assert svc.pending() == 2
    done = svc.run()
    assert [q.uid for q in done] == [1, 7] and all(q.done for q in done)
    want = oracle.query_batch(
        np.asarray([OPS["lca_level"], OPS["max_k"]], np.int32),
        np.asarray([1, 3], np.int32), np.asarray([5, 0], np.int32))
    assert [q.result for q in done] == list(want)
    # the batch retired: queued refcounts drained back to zero
    assert all(m.queued == 0 for m in pool.meta.values())


def test_validation_uses_true_dims_not_bucket_shape(tenant_dir):
    """An id inside the padded bucket but past the tenant's real range
    must be rejected host-side (the jitted gather would clamp and
    answer confidently wrong)."""
    pool = ForestPool(slots=8, artifact_dir=tenant_dir)
    svc = MultiTenantService(pool, batch=32)
    pool.ensure("small0")
    n_ent = pool.meta["small0"].n_entities
    with pytest.raises(ValueError, match="out of range"):
        svc.query_batch(["small0"], np.asarray([OPS["max_k"]], np.int32),
                        np.asarray([n_ent], np.int32))
    with pytest.raises(ValueError, match="unknown op"):
        svc.submit(MTQuery(uid=0, tenant="small0", op="nope", a=0))


# ---------------------------------------------- compile-count invariants
def test_one_compile_per_bucket_and_zero_retrace_cold_load(tenant_dir):
    """Exactly one compiled dispatch per shape bucket, and admitting a
    cold tenant into an existing bucket must not add one (values
    change, shapes don't)."""
    multiserve._answer_batch_multi._clear_cache()
    pool = ForestPool(slots=8, artifact_dir=tenant_dir)
    svc = MultiTenantService(pool, batch=64)
    tenants, ops, a, b = _workload_all(pool, ["big0", "big1", "small0"])
    svc.query_batch(tenants, ops, a, b)
    assert multiserve.compiled_dispatch_count() == len(pool.buckets)
    # cold admissions + more traffic: the cache tracks the BUCKET
    # count, never the tenant count
    tenants, ops, a, b = _workload_all(
        pool, ["big0", "big1", "big2", "big3", "small0", "small1"], seed=2)
    svc.query_batch(tenants, ops, a, b)
    assert multiserve.compiled_dispatch_count() == len(pool.buckets)


# ------------------------------------------------------- LRU + eviction
def test_lru_order_under_interleaved_query_and_load(tenant_dir):
    """With 2 slots in the big bucket's budget, the least-recently-
    QUERIED tenant is the one evicted — interleaved traffic reorders
    the victim choice."""
    pool = ForestPool(slots=2, artifact_dir=tenant_dir)
    svc = MultiTenantService(pool, batch=16)
    pool.ensure("big0")
    pool.ensure("big1")
    # traffic touches big0 AFTER big1's admission → big1 is now LRU
    svc.query_batch(["big0"], np.asarray([OPS["max_k"]], np.int32),
                    np.asarray([0], np.int32))
    pool.ensure("big2")                      # must evict big1, not big0
    assert pool.resident("big0") and pool.resident("big2")
    assert not pool.resident("big1")
    assert pool.stats()["evictions"] == 1


def test_pinned_tenant_never_evicted(tenant_dir):
    pool = ForestPool(slots=2, artifact_dir=tenant_dir)
    pool.pin("big0")
    for t in ("big1", "big2", "big3"):
        pool.ensure(t)
    assert pool.resident("big0")
    with pytest.raises(ValueError, match="pinned"):
        pool.evict("big0")
    pool.unpin("big0")
    pool.ensure("small0")                    # now big0 is fair game
    assert not pool.resident("big0")


def test_queued_tenant_never_evicted_and_poolfull(tenant_dir):
    pool = ForestPool(slots=1, artifact_dir=tenant_dir)
    pool.ensure("big0")
    pool.note_queued("big0", +1)
    with pytest.raises(PoolFull):
        pool.ensure("big1")
    with pytest.raises(ValueError, match="queued"):
        pool.evict("big0")
    pool.note_queued("big0", -1)
    pool.ensure("big1")                      # retired batch → evictable
    assert not pool.resident("big0")


def test_evict_reload_answers_bit_identical(tenant_dir):
    """A tenant evicted and later re-admitted (different slot, possibly
    grown bucket) answers exactly as a pool that never evicted it."""
    tenants_ops = None
    answers = []
    for slots in (8, 3):                     # never-evicts vs thrashes
        pool = ForestPool(slots=slots, artifact_dir=tenant_dir)
        svc = MultiTenantService(pool, batch=32)
        if tenants_ops is None:
            for t in ("big0", "big1", "big2"):
                pool.ensure(t)
            tenants_ops = _workload(pool, ["big0", "big1", "big2"], 120,
                                    seed=3)
        t_col, ops, a, b = tenants_ops
        if slots == 3:                       # force churn before serving
            for t in ("big0", "big1", "big2", "big3", "big0"):
                pool.ensure(t)
            assert pool.stats()["evictions"] >= 2
        answers.append(svc.query_batch(t_col, ops, a, b))
    np.testing.assert_array_equal(answers[0], answers[1])


def test_admission_cannot_evict_tenant_of_same_batch(tenant_dir):
    """A batch referencing a resident tenant plus a cold one, on a pool
    with no headroom: the cold load must not evict the co-batched
    resident tenant (it raises PoolFull instead of serving wrong)."""
    pool = ForestPool(slots=1, artifact_dir=tenant_dir)
    svc = MultiTenantService(pool, batch=16)
    pool.ensure("big0")
    ops = np.asarray([OPS["max_k"]] * 2, np.int32)
    z = np.zeros(2, np.int32)
    with pytest.raises(PoolFull):
        svc.query_batch(["big0", "big1"], ops, z, z)
    assert pool.resident("big0")
    assert all(m.queued == 0 for m in pool.meta.values())  # pins released


def test_serve_spans_one_per_call_and_per_chunk(tenant_dir):
    """Spans-only obs: one ``serve.query_batch`` (with admit and pack)
    per call, one ``serve.dispatch`` per dispatched chunk, and the same
    answers as with obs off."""
    from repro import obs

    pool = ForestPool(slots=8, artifact_dir=tenant_dir)
    svc = MultiTenantService(pool, batch=32)
    calls = [_workload_all(pool, ["big0", "big1", "small0"], n=n, seed=n)
             for n in (100, 7)]
    want = [svc.query_batch(*c) for c in calls]
    d0 = svc.dispatches
    obs.disable()
    t = obs.enable(timeline=False)
    try:
        got = [svc.query_batch(*c) for c in calls]
    finally:
        obs.disable()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    by_name = {}
    for e in t.spans("serve"):
        by_name.setdefault(e["name"], []).append(e)
    qb = by_name["serve.query_batch"]
    assert len(qb) == len(calls)
    assert [e["args"]["n"] for e in qb] == [len(c[0]) for c in calls]
    assert [e["args"]["buckets"] for e in qb] == [2, 2]
    assert len(by_name["serve.admit"]) == len(calls)
    assert len(by_name["serve.pack"]) == len(calls)
    disp = by_name["serve.dispatch"]
    chunks = 0
    for tenants, *_ in calls:
        per_bucket = {}
        for x in tenants:
            key = pool.meta[x].bucket
            per_bucket[key] = per_bucket.get(key, 0) + 1
        chunks += sum(-(-c // svc.batch) for c in per_bucket.values())
    assert len(disp) == svc.dispatches - d0 == chunks
    assert sum(e["args"]["n"] for e in disp) == 107
    for q in qb:                      # every dispatch inside its call
        inner = [e for e in disp
                 if q["ts"] <= e["ts"] <= q["ts"] + q["dur"]]
        assert sum(e["args"]["n"] for e in inner) == q["args"]["n"]


# --------------------------------------------------- artifact versions
def test_v1_artifact_loads_through_loader_branch(tenant_dir, tmp_path):
    """Old-format artifacts written before the pack cache existed must
    keep loading (and serving) through the v1 loader branch."""
    h = _hier(seed=0)
    p1 = str(tmp_path / "old.npz")
    save_hierarchy(p1, h, version=1)
    h1 = load_hierarchy(p1)
    assert "pack_up" not in h1.meta          # v1 carries no pack cache
    np.testing.assert_array_equal(h1.theta, h.theta)

    p2 = str(tmp_path / "new.npz")
    save_hierarchy(p2, h)                    # current version
    h2 = load_hierarchy(p2)
    assert h2.meta["pack_up"].shape[0] == h.n_nodes
    # both versions produce identical packed forests
    f1, f2 = pack_forest(h1), pack_forest(h2)
    np.testing.assert_array_equal(np.asarray(f1.up), np.asarray(f2.up))
    np.testing.assert_array_equal(np.asarray(f1.depth),
                                  np.asarray(f2.depth))


def test_pool_serves_v1_and_v2_tenants_identically(tmp_path):
    d = str(tmp_path)
    h = _hier(seed=5)
    save_hierarchy(os.path.join(d, "v1t.npz"), h, version=1)
    save_hierarchy(os.path.join(d, "v2t.npz"), h)
    pool = ForestPool(slots=4, artifact_dir=d)
    svc = MultiTenantService(pool, batch=16)
    t_col, ops, a, b = _workload_all(pool, ["v1t"], n=60, seed=4)
    got1 = svc.query_batch(t_col, ops, a, b)
    got2 = svc.query_batch(["v2t"] * len(t_col), ops, a, b)
    np.testing.assert_array_equal(got1, got2)


def test_format_version_bumped_for_pack_cache():
    assert FORMAT_VERSION == 2


def test_unwritable_version_rejected(tmp_path):
    with pytest.raises(ValueError, match="cannot write"):
        save_hierarchy(str(tmp_path / "x.npz"), _hier(nu=12, nv=8, m=24),
                       version=99)


def test_obs_off_dispatch_jaxpr_byte_identical(obs_golden):
    """Zero-overhead-off for the serving layer: the batched multi-tenant
    dispatch jaxpr with telemetry disabled equals the
    pre-instrumentation golden byte-for-byte — the serve spans/metrics
    are host-side only and must never enter the compiled program."""
    from repro import obs

    rec, golden = obs_golden
    assert not obs.enabled()
    got = rec.CASES["multiserve_dispatch"]()
    assert got == golden["multiserve_dispatch"], \
        "dispatch jaxpr drifted from the telemetry-off golden"
