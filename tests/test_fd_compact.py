"""The compacting wing FD driver ≡ the single-launch device loop and the
host driver: θ, per-partition rounds, ρ_fd and update counts, while the
wedge list shrinks as its wedges die (``peel._fd_wing_compacting``)."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import peel
from repro.core.graph import BipartiteGraph, powerlaw_bipartite, random_bipartite
from repro.core.peel import build_peel_spec, wing_decomposition
from repro.core.peelspec import PeelStats, cd_loop, run_fd

ROOT = Path(__file__).resolve().parents[1]


def _graph500(scale: int) -> BipartiteGraph:
    """The benchmark's Kronecker graph (A=0.57, B=0.19, C=0.19) at
    ``scale``, read as bipartite."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench.generators import kronecker

    cfg = json.loads((ROOT / "bench/configs/graph500.json").read_text())
    cfg["scale"] = scale
    n_u, n_v, edges = kronecker.edges(cfg)
    return BipartiteGraph.from_edges(n_u, n_v, edges)


@pytest.fixture
def small_floor(monkeypatch):
    """Shrink the ladder's floor so small graphs compact too; the chunk
    program reads the floor when traced, so its cache is cleared on both
    sides."""
    peel._fd_wing_chunk.clear_cache()
    monkeypatch.setattr(peel, "_FD_COMPACT_FLOOR", 128)
    yield
    peel._fd_wing_chunk.clear_cache()


@pytest.fixture
def against_single_launch(monkeypatch):
    """Run :func:`peel._fd_wing_device` beside every compacting launch
    on the same inputs and require identical θ, rounds and updates."""
    real = peel._fd_wing_compacting
    calls = []

    def both(*args, part):
        got = real(*args, part=part)
        want = peel._fd_wing_device(*args)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w)), part
        calls.append(part)
        return got

    monkeypatch.setattr(peel, "_fd_wing_compacting", both)
    return calls


def _per_partition(g, fd_driver, only=None, P=16):
    """θ, PeelStats and {part: (rounds, updates, recounts)} of one csr
    wing peel driven through ``cd_loop`` / ``run_fd``."""
    stats = PeelStats(engine="csr", fd_driver=fd_driver)
    spec = build_peel_spec(g, "wing", stats, engine="csr",
                           fd_driver=fd_driver)
    part, sup_init, _, p_eff = cd_loop(spec, P, stats)
    theta = np.zeros(spec.n, dtype=np.int64)
    per = {}
    run_fd(spec, part, sup_init, theta, p_eff, stats, fd_driver=fd_driver,
           only=only, per_partition=per)
    return theta, stats, per, part


def _assert_same(a, b):
    theta_a, stats_a, per_a, _ = a
    theta_b, stats_b, per_b, _ = b
    assert np.array_equal(theta_a, theta_b)
    assert per_a == per_b
    for k in ("rho_cd", "rho_fd_total", "rho_fd_max", "updates",
              "p_effective"):
        assert getattr(stats_a, k) == getattr(stats_b, k), k


GRAPHS = {
    "random-a": lambda: random_bipartite(40, 30, 260, seed=1),
    "random-b": lambda: random_bipartite(60, 25, 400, seed=7),
    "powerlaw": lambda: powerlaw_bipartite(80, 40, 420, seed=2),
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_compacting_matches_single_launch_and_host(
        name, small_floor, against_single_launch):
    g = GRAPHS[name]()
    tracer = obs.enable(timeline=False)
    try:
        dev = _per_partition(g, "device")
        n_compact = tracer.count(cat="fd.compact")
    finally:
        obs.disable()
    host = _per_partition(g, "host")
    _assert_same(dev, host)
    assert sorted(against_single_launch) == sorted(dev[2])
    assert n_compact >= 1


def test_compaction_engages_on_kronecker_scale8(against_single_launch):
    """At the real floor: a graph whose largest partition carries over
    20,000 wedges shrinks it at least twice, with every count equal."""
    g = _graph500(8)
    tracer = obs.enable(timeline=False)
    try:
        dev = _per_partition(g, "device")
        spans = tracer.spans(cat="fd.compact")
    finally:
        obs.disable()
    assert len(spans) >= 2
    assert max(s["args"]["size_from"] for s in spans) > 16_384
    for s in spans:
        a = s["args"]
        assert a["size_to"] < a["size_from"]
        assert a["live"] <= a["size_to"]
        assert a["size_to"] >= peel._FD_COMPACT_FLOOR
    _assert_same(dev, _per_partition(g, "host"))
    res = wing_decomposition(g, engine="csr")
    assert np.array_equal(res.theta, dev[0])


def test_streaming_only_subset_compacts_the_same(small_floor):
    """``run_fd(only=...)`` — streaming's re-peel of dirty partitions —
    takes the compacting driver with the host driver's results."""
    g = powerlaw_bipartite(80, 40, 420, seed=2)
    full = _per_partition(g, "device")
    ids = sorted(full[2])[::2]
    tracer = obs.enable(timeline=False)
    try:
        dev = _per_partition(g, "device", only=ids)
        parts = {s["args"]["part"] for s in tracer.spans(cat="fd.compact")}
    finally:
        obs.disable()
    host = _per_partition(g, "host", only=ids)
    assert sorted(dev[2]) == ids
    assert parts and parts <= set(ids)
    _assert_same(dev, host)
    sel = np.isin(full[3], ids)
    assert np.array_equal(dev[0][sel], full[0][sel])


def test_shrink_ladder():
    floor = peel._FD_COMPACT_FLOOR
    assert peel._wedge_shrink_limit(floor) == -1
    assert peel._wedge_shrink_limit(128) == -1
    assert peel._wedge_shrink_limit(2 * floor) == floor
    assert peel._wedge_shrink_limit(98_304) == 65_536
    assert peel._wedge_shrink_size(0) == floor
    assert peel._wedge_shrink_size(floor + 1) == 2 * floor
    assert peel._wedge_shrink_size(65_536) == 65_536
    # a relaunch always has a round to run: its live count is above the
    # new size's limit
    for live in (0, 1, floor, floor + 1, 40_000, 65_536):
        size = peel._wedge_shrink_size(live)
        assert live > peel._wedge_shrink_limit(size)


def test_compact_wedges_packs_live_in_order():
    alive = jnp.asarray([False, True, True, False, True, False, False, True])
    we = jnp.arange(8, dtype=jnp.int32) + 10
    a, e1, e2, wp = peel._compact_wedges(alive, we, we + 1, we + 2, size=4)
    assert np.asarray(a).tolist() == [True] * 4
    assert np.asarray(e1).tolist() == [11, 12, 14, 17]
    assert np.asarray(wp).tolist() == [13, 14, 16, 19]
    a, e1, _, _ = peel._compact_wedges(alive, we, we, we, size=8)
    assert np.asarray(a).tolist() == [True] * 4 + [False] * 4
    assert np.asarray(e1).tolist() == [11, 12, 14, 17, 0, 0, 0, 0]


def _chunk_jaxpr():
    m, n_pairs, size = 140, 64, 2 * peel._FD_COMPACT_FLOOR
    zero = jnp.int32(0)
    state = (jnp.zeros((m,), bool), jnp.zeros((m,), jnp.int32),
             (jnp.zeros((size,), bool), jnp.zeros((n_pairs,), jnp.int32)),
             jnp.zeros((m,), jnp.int32), zero, zero, zero)
    we = jnp.zeros((size,), jnp.int32)
    return str(jax.make_jaxpr(
        lambda *a: peel._fd_wing_chunk(*a, n_pairs=n_pairs, m=m))(
        state, we, we, we))


def test_chunk_program_is_one_while_and_ignores_spans():
    off = _chunk_jaxpr()
    obs.enable(timeline=False)
    try:
        on = _chunk_jaxpr()
    finally:
        obs.disable()
    assert on == off
    assert off.count("while[") == 1


def test_whole_graph_partition_matches_single_launch(small_floor):
    """Every edge in one partition: the compacting launches and one
    launch of :func:`peel._fd_wing_device` give the same results."""
    from repro.core import csr

    g = powerlaw_bipartite(60, 40, 300, seed=5)
    w = csr.build_wedges(g)
    args = (jnp.ones((g.m,), bool),
            jnp.asarray(csr.edge_butterflies0(w).astype(np.int32)),
            jnp.ones((w.n_wedges,), bool), jnp.asarray(w.W0.astype(np.int32)),
            jnp.asarray(w.wedge_e1), jnp.asarray(w.wedge_e2),
            jnp.asarray(w.wedge_pair))
    want = peel._fd_wing_device(*args, n_pairs=w.n_pairs, m=g.m)
    tracer = obs.enable(timeline=False)
    try:
        got = peel._fd_wing_compacting(*args, n_pairs=w.n_pairs, m=g.m)
        n_compact = tracer.count(cat="fd.compact")
    finally:
        obs.disable()
    assert n_compact >= 2
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
