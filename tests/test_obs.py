"""Observability layer (``src/repro/obs``).

Four claims under test:

* **Units** — tracer span/instant/counter recording, Chrome-trace
  export shape, metrics registry snapshots, timeline (de)serialization.
* **Exact-match oracle** — with the layer enabled, per-phase trace
  span counts equal the run's :class:`PeelStats` *exactly*
  (``cd.round`` count == ``rho_cd``, ``fd.round`` count ==
  ``rho_fd_total``), across engines and FD drivers, single-node and
  distributed; and enabling telemetry never changes θ.  In spans-only
  mode (``enable(timeline=False)``) the same holds for ``cd.round`` /
  ``cd.step`` / ``cd.select`` / ``fd.partition`` / ``hierarchy.assemble``,
  with no timeline and no ``*_rings`` FD program called.
* **Serving metrics oracle** — pool cache counters mirror the pool's
  plain-int LRU bookkeeping one-for-one; per-slot admission upload is
  bit-identical to the whole-bucket re-upload it replaces.
* **Graceful shutdown** — ``launch/hserve.py`` under SIGINT drains the
  queue, flushes the metrics snapshot, and exits 0 (subprocess
  regression); the snapshot's cache counts match the ``--out`` oracle.

The zero-overhead-off guarantee (byte-identical jaxprs with telemetry
disabled) is asserted against ``tests/goldens/obs_jaxprs.json`` in
``test_fused_fd.py`` / ``test_multiserve.py`` /
``test_core_distributed.py`` next to the structural invariants those
suites already state.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from repro import obs
from repro.core import peel as peel_mod
from repro.core import peelspec
from repro.core.graph import powerlaw_bipartite, random_bipartite
from repro.core.peel import tip_decomposition, wing_decomposition
from repro.hierarchy import (
    ForestPool,
    MultiTenantService,
    build_hierarchy,
    save_hierarchy,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracer():
    """A fresh tracer per test; always disabled afterwards so the rest
    of the suite keeps the zero-overhead default path."""
    obs.disable()
    t = obs.enable()
    yield t
    obs.disable()


# =====================================================================
# units: tracer
# =====================================================================
def test_tracer_records_and_exports(tracer, tmp_path):
    with obs.span("outer", cat="peel", kind="wing"):
        with obs.span("inner", cat="cd") as sp:
            sp.update(died=3, frontier=7)
        obs.instant("tick", cat="fd.round", part=0)
        obs.counter("curve", {"frontier": 7})
    assert tracer.count("peel") == 1
    assert tracer.count("cd") == 1
    assert tracer.count("fd.round", ph="i") == 1
    assert tracer.count(ph="C") == 1
    # late args land on the span event
    (inner,) = tracer.spans("cd")
    assert inner["args"] == {"died": 3, "frontier": 7}
    assert inner["dur"] >= 0
    # nesting: outer span encloses inner on the timeline
    (outer,) = tracer.spans("peel")
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert tracer.sum_arg("died", cat="cd") == 3
    # chrome envelope: standard keys, JSON-serializable, round-trips
    path = str(tmp_path / "trace.json")
    tracer.save(path)
    with open(path) as f:
        chrome = json.load(f)
    assert set(chrome) == {"traceEvents", "displayTimeUnit"}
    assert len(chrome["traceEvents"]) == 4
    for ev in chrome["traceEvents"]:
        assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert "dur" in ev


def test_disabled_layer_is_inert():
    obs.disable()
    assert not obs.enabled()
    assert obs.get_tracer() is None
    with obs.span("ghost", cat="peel") as sp:
        assert sp is None
    obs.instant("ghost")
    obs.counter("ghost", {"x": 1})
    with obs.maybe_collect() as col:
        assert col is None
        assert obs.fd_ring_cap() == 0


def test_ring_cap_env(tracer, monkeypatch):
    with obs.maybe_collect():
        assert obs.fd_ring_cap() == obs.RING_CAP_DEFAULT
        monkeypatch.setenv("REPRO_OBS_RING_CAP", "17")
        assert obs.fd_ring_cap() == 17
        monkeypatch.setenv("REPRO_OBS_RING_CAP", "bogus")
        assert obs.fd_ring_cap() == obs.RING_CAP_DEFAULT
    assert obs.fd_ring_cap() == 0        # no live collector


# =====================================================================
# units: metrics
# =====================================================================
def test_metrics_registry_snapshot(tmp_path):
    reg = obs.MetricsRegistry()
    reg.inc("ops")
    reg.inc("ops", 4)
    reg.set_gauge("depth", 3)
    reg.set_gauge("depth", 9)
    for ms in (0.5, 1.0, 2.0, 4.0, 400.0):
        reg.observe("lat", ms)
    reg.histogram("empty")
    snap = reg.snapshot()
    assert snap["ops"] == {"type": "counter", "value": 5}
    assert snap["depth"] == {"type": "gauge", "value": 9.0}
    assert snap["empty"] == {"type": "histogram", "count": 0}
    lat = snap["lat"]
    assert lat["count"] == 5
    assert lat["sum_ms"] == pytest.approx(407.5)
    assert lat["min_ms"] == 0.5 and lat["max_ms"] == 400.0
    # percentiles are bucket-interpolated but clamped and ordered
    assert lat["min_ms"] <= lat["p50_ms"] <= lat["p99_ms"] <= lat["max_ms"]
    path = str(tmp_path / "metrics.json")
    reg.save(path)
    with open(path) as f:
        assert json.load(f) == snap
    with pytest.raises(TypeError):
        reg.observe("ops", 1.0)          # name already bound to a counter


def test_percentiles_exact():
    samples = list(range(101))           # 0..100
    ps = obs.percentiles(samples)
    assert ps == {"p50": 50.0, "p99": 99.0}
    assert obs.percentiles([]) == {"p50": 0.0, "p99": 0.0}
    one = obs.percentiles([7.0], ps=(50.0, 90.0, 99.0))
    assert one == {"p50": 7.0, "p90": 7.0, "p99": 7.0}


# =====================================================================
# units: timeline
# =====================================================================
def test_timeline_collector_and_roundtrip():
    col = obs.TimelineCollector()
    col.record_cd_round(0, died=5, frontier=20, hi=3, updates=12,
                        recounts=2)
    col.record_cd_round(1, died=20, frontier=0, hi=9, updates=7,
                        recounts=0)
    col.record_fd_host(0, [dict(died=2, frontier=3, k=1),
                           dict(died=3, frontier=0, k=2)])
    rings = (np.array([4, 1, 0]), np.array([6, 0, 0]),
             np.array([1, 2, 0]), np.array([[8], [3], [0]]))
    col.record_fd_rings("device", parts=[1], rounds=[2], rings=rings,
                        cap=3)
    col.record_fd_counts("sharded", parts=[0, 1, 2], rounds=[3, 0, 4])
    tl = col.build()
    assert tl.cd_rounds == 2
    assert tl.fd_rounds_total() == 2 + 2 + 7
    assert tl.fd_rounds_max() == 4
    assert tl.updates_total() == 12 + 7 + 8 + 3
    assert not tl.truncated()
    s = tl.summary()
    assert s["cd_rounds"] == 2 and s["fd_launches"] == 3
    assert s["fd_rounds_total"] == 11 and s["cd_died_max"] == 20
    # counts-only launches have no per-round detail (T == 0)
    assert tl.fd[2]["died"].shape == (0, 3)
    # dict round trip preserves every total
    tl2 = obs.PeelTimeline.from_dict(
        json.loads(json.dumps(tl.as_dict())))
    assert tl2.cd_rounds == tl.cd_rounds
    assert tl2.fd_rounds_total() == tl.fd_rounds_total()
    assert tl2.updates_total() == tl.updates_total()
    assert tl2.summary() == s


def test_timeline_ring_truncation():
    col = obs.TimelineCollector()
    rings = (np.array([1, 1]), np.array([9, 0]),
             np.array([1, 5]), np.array([[2], [2]]))
    col.record_fd_rings("device", parts=[0], rounds=[5], rings=rings,
                        cap=2)
    tl = col.build()
    assert tl.truncated()
    assert tl.fd_rounds_total() == 5     # round totals stay exact
    assert tl.fd[0]["died"].shape == (2, 1)


# =====================================================================
# the exact-match oracle: span counts == PeelStats, θ unchanged
# =====================================================================
WING_COMBOS = [
    ("beindex", "device", False),
    ("beindex", "host", False),
    ("csr", "device", False),
    ("csr", "vmapped", False),
    ("csr", "device", True),             # fused
]
TIP_COMBOS = [
    ("dense", "device", False),
    ("dense", "host", False),
    ("csr", "device", False),
    ("csr", "vmapped", False),
    ("csr", "device", True),             # fused
]


def _assert_exact_match(run):
    """θ with telemetry on == θ off; trace counts == PeelStats."""
    obs.disable()
    base = run()
    t = obs.enable()
    try:
        res = run()
    finally:
        obs.disable()
    np.testing.assert_array_equal(res.theta, base.theta)
    st = res.stats
    assert res.timeline is not None
    assert res.timeline.cd_rounds == st.rho_cd
    assert res.timeline.fd_rounds_total() == st.rho_fd_total
    assert t.count("cd.round", ph="X") == st.rho_cd
    assert t.count("fd.round", ph="i") == st.rho_fd_total
    assert t.count("peel", ph="X") == 1
    assert t.count("cd", ph="X") == 1
    assert t.count("fd", ph="X") == 1
    assert res.provenance()["timeline"]["cd_rounds"] == st.rho_cd


# every telemetry-on FD entry: spans-only mode must call none of them
RING_ENTRIES = [
    (peel_mod, name) for name in (
        "_fd_tip_vmapped_rings", "_fd_wing_vmapped_rings",
        "_fd_wing_vmapped_pallas_rings", "_fd_wing_fused_rings",
        "_fd_tip_fused_rings", "_fd_tip_device_rings",
        "_fd_wing_device_rings")
] + [
    (peelspec, name) for name in (
        "_fd_while_device_rings", "_fd_while_vmapped_rings",
        "_fd_while_fused_rings")
]


def _refuse_rings(monkeypatch):
    for mod, name in RING_ENTRIES:
        def refuse(*a, _name=name, **kw):
            raise AssertionError(f"{_name} called in spans-only mode")

        monkeypatch.setattr(mod, name, refuse)


def _assert_spans_only(run, build, fd_driver, monkeypatch):
    """Spans-only mode (``enable(timeline=False)``): θ == the obs-off
    run, no timeline, no ring program, and the CD / FD / build span
    counts equal PeelStats."""
    obs.disable()
    base = run()
    _refuse_rings(monkeypatch)
    t = obs.enable(timeline=False)
    try:
        assert obs.enabled() and not obs.timeline_enabled()
        res = run()
        build(res)
    finally:
        obs.disable()
    np.testing.assert_array_equal(res.theta, base.theta)
    assert res.timeline is None
    assert "timeline" not in res.provenance()
    st = res.stats
    assert t.count("cd.round", ph="X") == st.rho_cd
    assert t.count("cd.step", ph="X") == st.rho_cd
    assert t.count("cd.select", ph="X") == st.p_effective
    assert t.count("fd.round", ph="i") == 0
    n_part = sum(e["name"] == "fd.partition" for e in t.spans("fd.launch"))
    if fd_driver == "vmapped":
        assert n_part == 0
        assert t.count("fd.launch") == 1
    else:
        assert n_part == st.p_effective
        parts = sorted(e["args"]["part"] for e in t.spans("fd.launch"))
        assert parts == list(range(st.p_effective))
        assert t.sum_arg("rounds", cat="fd.launch") == st.rho_fd_total
    select = t.spans("cd.select")
    assert [e["args"]["part"] for e in select] == list(range(st.p_effective))
    assert [e["args"]["hi"] for e in select] == res.ranges[1:].tolist()
    assert t.sum_arg("died", cat="cd.step") == res.theta.size
    assert t.count("peel", ph="X") == 1
    assert sum(e["name"] == "hierarchy.assemble"
               for e in t.spans("hierarchy")) == 1
    assert sum(e["name"] == "hierarchy.build"
               for e in t.spans("hierarchy")) == 1


def test_spans_enter_the_jax_profile_as_repro(tmp_path):
    """Each span is a ``repro.<name>`` annotation in a concurrent jax
    profile, on the profiler's clock beside the device work."""
    import gzip
    import glob

    import jax

    obs.disable()
    obs.enable(timeline=False)
    try:
        jax.profiler.start_trace(str(tmp_path), create_perfetto_trace=True)
        try:
            with obs.span("cd.step", cat="cd.step"):
                jax.block_until_ready(jax.numpy.arange(8) + 1)
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.disable()
    (path,) = glob.glob(str(tmp_path / "**" / "perfetto_trace.json.gz"),
                        recursive=True)
    with gzip.open(path, "rt") as fh:
        names = {e.get("name") for e in json.load(fh)["traceEvents"]}
    assert obs.ANNOTATION_PREFIX + "cd.step" in names
    assert "cd.step" not in names


def test_spans_only_mode_installs_no_collector():
    obs.disable()
    obs.enable(timeline=False)
    try:
        with obs.maybe_collect() as col:
            assert col is None
            assert obs.active_collector() is None
            assert obs.fd_ring_cap() == 0
        obs.enable()                     # back to timeline mode
        assert obs.timeline_enabled()
        with obs.maybe_collect() as col:
            assert col is not None
            assert obs.fd_ring_cap() == obs.RING_CAP_DEFAULT
    finally:
        obs.disable()
    assert not obs.timeline_enabled()


def _modes(combos):
    """Each combo in timeline mode (under its plain id) and in
    spans-only mode."""
    return ([pytest.param(*c, True, id="-".join(map(str, c)))
             for c in combos]
            + [pytest.param(*c, False, id="-".join(map(str, c)) + "-spans")
               for c in combos])


@pytest.mark.parametrize("engine,fd_driver,fused,timeline",
                         _modes(WING_COMBOS))
def test_wing_trace_counts_match_stats(engine, fd_driver, fused, timeline,
                                       monkeypatch):
    g = random_bipartite(30, 24, 140, seed=1)

    def run():
        return wing_decomposition(g, P=4, engine=engine,
                                  fd_driver=fd_driver, fused=fused)

    if timeline:
        _assert_exact_match(run)
    else:
        _assert_spans_only(run, lambda res: build_hierarchy(g, res),
                           fd_driver, monkeypatch)


@pytest.mark.parametrize("engine,fd_driver,fused,timeline",
                         _modes(TIP_COMBOS))
def test_tip_trace_counts_match_stats(engine, fd_driver, fused, timeline,
                                      monkeypatch):
    g = random_bipartite(30, 24, 140, seed=1)

    def run():
        return tip_decomposition(g, side="u", P=4, engine=engine,
                                 fd_driver=fd_driver, fused=fused)

    if timeline:
        _assert_exact_match(run)
    else:
        _assert_spans_only(
            run, lambda res: build_hierarchy(g, res, kind="tip", side="u"),
            fd_driver, monkeypatch)


def test_distributed_trace_counts_match_stats():
    """8-device wing+tip with telemetry on: the sharded FD records
    counts-only launches, but totals must still equal PeelStats and the
    info dict must carry the timeline summary (subprocess for the
    forced host device count)."""
    src = """
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro import obs
        from repro.core.graph import random_bipartite
        from repro.core import distributed as D
        obs.enable()
        tr = obs.get_tracer()
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        g = random_bipartite(60, 40, 260, seed=3)
        for kind, fn, kw in (
            ("wing", D.distributed_wing_decomposition,
             dict(engine="csr")),
            ("tip", D.distributed_tip_decomposition,
             dict(side="u", engine="csr")),
        ):
            n0_cd = tr.count("cd.round", ph="X")
            n0_fd = tr.count("fd.round", ph="i")
            theta, info, res = fn(g, mesh, P_parts=8,
                                  return_result=True, **kw)
            st = res.stats
            tl = res.timeline
            assert tl is not None, kind
            assert info["timeline"] == tl.summary(), kind
            assert tl.cd_rounds == st.rho_cd, kind
            assert tl.fd_rounds_total() == st.rho_fd_total, kind
            d_cd = tr.count("cd.round", ph="X") - n0_cd
            d_fd = tr.count("fd.round", ph="i") - n0_fd
            assert d_cd == st.rho_cd, (kind, d_cd, st.rho_cd)
            assert d_fd == st.rho_fd_total, (kind, d_fd, st.rho_fd_total)
        print("DIST-OBS-OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "DIST-OBS-OK" in out.stdout


# =====================================================================
# serving metrics: the pool LRU oracle + per-slot admission parity
# =====================================================================
def _hier(nu=40, nv=28, m=120, seed=0):
    g = powerlaw_bipartite(nu, nv, m, seed=seed)
    return build_hierarchy(g, wing_decomposition(g, P=4, engine="csr"))


@pytest.fixture(scope="module")
def art_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("obs_tenants")
    hs = [_hier(seed=i) for i in range(5)]
    for i, h in enumerate(hs):
        save_hierarchy(str(d / f"t{i}.npz"), h)
    # same decomposition under a second name: guaranteed same shape
    # bucket as t0 (the slot-upload parity test relies on this)
    save_hierarchy(str(d / "dup0.npz"), hs[0])
    return str(d)


def test_pool_metrics_match_lru_oracle(art_dir):
    pool = ForestPool(slots=3, artifact_dir=art_dir)
    # misses t0..t2 fill the pool; t0/t1 hits re-rank them; t3 and t4
    # evict; the final t2 re-load is a miss evicting again
    for t in ("t0", "t1", "t2", "t0", "t1", "t3", "t4", "t2"):
        pool.ensure(t)
    assert (pool.hits, pool.misses, pool.evictions) == (2, 6, 3)
    snap = pool.metrics.snapshot()
    assert snap["pool.hits"]["value"] == pool.hits
    assert snap["pool.misses"]["value"] == pool.misses
    assert snap["pool.evictions"]["value"] == pool.evictions
    assert snap["pool.resident"]["value"] == pool.resident_count == 3
    assert snap["pool.load_ms"]["count"] == pool.misses
    # the plain-int stats dict and the registry never diverge
    st = pool.stats()
    for key in ("hits", "misses", "evictions"):
        assert snap[f"pool.{key}"]["value"] == st[key]


def test_service_shares_pool_registry(art_dir):
    pool = ForestPool(slots=4, artifact_dir=art_dir)
    svc = MultiTenantService(pool, batch=32)
    assert svc.metrics is pool.metrics
    n = 80
    rng = np.random.default_rng(0)
    tenants = [("t0", "t1")[i % 2] for i in range(n)]
    ops = np.zeros(n, np.int32)          # op 0 needs only entity ids
    a = rng.integers(0, 10, n).astype(np.int32)
    svc.query_batch(tenants, ops, a)
    snap = svc.metrics.snapshot()
    assert snap["serve.served"]["value"] == n
    assert snap["serve.dispatches"]["value"] == svc.dispatches
    assert snap["serve.dispatch_ms"]["count"] == svc.dispatches
    assert snap["serve.tenant.t0"]["value"] == n // 2
    assert snap["serve.tenant.t1"]["value"] == n // 2
    # padded slots: per dispatch, batch - served_in_chunk
    padded = snap["serve.slots_padded"]["value"]
    assert padded == svc.dispatches * 32 - n


def test_slot_admission_parity(art_dir):
    """Per-slot dynamic_update_slice admission must leave device arrays
    bit-identical to the whole-bucket re-upload path, and identical to
    the host mirror."""
    arrs = {}
    pools = {}
    for mode, su in (("slot", True), ("bucket", False)):
        pool = ForestPool(slots=8, artifact_dir=art_dir, slot_upload=su)
        pool.ensure("t0")
        for key in list(pool.buckets):
            pool.bucket_arrays(key)      # device-resident before admit
        pool.ensure("dup0")              # same bucket as t0 by design
        arrs[mode] = {
            key: {n: np.asarray(a)
                  for n, a in pool.bucket_arrays(key).items()}
            for key in pool.buckets
        }
        pools[mode] = pool
    assert arrs["slot"].keys() == arrs["bucket"].keys()
    for key in arrs["slot"]:
        for name in arrs["slot"][key]:
            np.testing.assert_array_equal(
                arrs["slot"][key][name], arrs["bucket"][key][name])
    for key, bucket in pools["slot"].buckets.items():
        for name, host in bucket.host.items():
            np.testing.assert_array_equal(
                np.asarray(bucket.device[name]), host)
    # the slot path observed an admission upload; the bucket path paid
    # a re-upload instead
    m_slot = pools["slot"].metrics.get("pool.admission_upload_ms")
    assert m_slot is not None and m_slot.count == 1
    assert pools["bucket"].metrics.get(
        "pool.admission_upload_ms") is None
    m_re = pools["bucket"].metrics.get("pool.bucket_upload_ms")
    assert m_re is not None and m_re.count >= 2


# =====================================================================
# hserve graceful shutdown (subprocess regression)
# =====================================================================
def test_hserve_sigint_graceful_exit(art_dir, tmp_path):
    """SIGINT mid-serve: drains, flushes metrics, exits 0; the metrics
    snapshot's cache counts match the ``--out`` LRU oracle."""
    metrics_path = str(tmp_path / "metrics.json")
    out_path = str(tmp_path / "out.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["PYTHONUNBUFFERED"] = "1"
    # a workload too large to finish before the signal: exit 0 can only
    # mean the graceful path ran (the handler is installed right after
    # the warm print, so any SIGINT from then on is honored)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.hserve",
         "--artifact-dir", art_dir, "--pool-slots", "4",
         "--batch", "64", "--queries", "2000000",
         "--metrics", metrics_path, "--out", out_path],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
    )
    try:
        head = []
        for line in proc.stdout:         # unbuffered: arrives live
            head.append(line)
            if "warmed" in line:
                break
        assert any("warmed" in ln for ln in head), "".join(head)
        time.sleep(0.5)
        proc.send_signal(signal.SIGINT)
        stdout, stderr = proc.communicate(timeout=300)
        stdout = "".join(head) + stdout
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, (stdout[-2000:], stderr[-2000:])
    assert "shutdown signal: queue drained" in stdout
    with open(out_path) as f:
        oracle = json.load(f)
    assert oracle["served"] < 2_000_000           # actually interrupted
    with open(metrics_path) as f:
        snap = json.load(f)
    for key in ("hits", "misses", "evictions"):
        # a counter never incremented is absent from the registry == 0
        got = snap.get(f"pool.{key}", {}).get("value", 0)
        assert got == oracle[key], key
    assert snap["pool.resident"]["value"] == oracle["resident"]
    assert "serve.qps" in snap
