"""Tip decomposition of a hub side whose counts exceed int32, and the
pair list from one adjacency product.

Supports, pair butterfly counts and θ above 2^31 come out exact through
``tip_decomposition``, ``build_hierarchy`` and ``launch/peel.py --edges
… --kind tip --side v``, against the benchmark's plain reference
(``bench/reference.py``) and ``core/ref.bup_tip_ref``; the product's
pair list equals the wedge list's, pair for pair and count for count;
the pair source and the count width are chosen from what the graph
shows."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core import csr
from repro.core.graph import BipartiteGraph
from repro.core.peel import PeelStats, build_peel_spec, tip_decomposition
from repro.core.peelspec import decompose
from repro.core.ref import bup_tip_ref
from repro.hierarchy import build_hierarchy

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, reference  # noqa: E402

INT32_MAX = 2 ** 31 - 1


def _complete(n_u: int, n_v: int) -> BipartiteGraph:
    e = np.stack(np.meshgrid(np.arange(n_u), np.arange(n_v),
                             indexing="ij"), -1).reshape(-1, 2)
    return BipartiteGraph.from_edges(n_u, n_v, e)


def _planted_hubs(seed: int = 5, n_u: int = 80_000, n_v: int = 40,
                  hubs: int = 3, p_hub: float = 0.95) -> BipartiteGraph:
    """Every U vertex tags two random non-hub V vertices; each of the
    ``hubs`` V hubs tags a random ``p_hub`` share of U."""
    rng = np.random.default_rng(seed)
    parts = [np.stack([np.repeat(np.arange(n_u), 2),
                       rng.integers(hubs, n_v, 2 * n_u)], axis=1)]
    for h in range(hubs):
        us = np.flatnonzero(rng.random(n_u) < p_hub)
        parts.append(np.stack([us, np.full(us.size, h)], axis=1))
    return BipartiteGraph.from_edges(n_u, n_v, np.concatenate(parts))


GRAPHS = {"complete_70000x4": lambda: _complete(70_000, 4),
          "planted_hubs": _planted_hubs}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def wide(request):
    """(name, graph, reference θ of side v, reference forest)."""
    g = GRAPHS[request.param]()
    theta = reference.tip_numbers(g.n_u, g.n_v, g.edges, "v")
    forest = reference.build_forest("tip", g.n_u, g.n_v, g.edges, theta,
                                    "v")
    return request.param, g, theta, forest


def _forest_wrong(g, res, h, forest) -> dict:
    out = dict(edges=g.edges, theta=res.theta,
               forest={f: getattr(h, f) for f in check.FOREST_FIELDS})
    return check.compare_job("tip", out, reference.canonical_edges(g.edges),
                             forest)


def test_the_graphs_exceed_int32(wide):
    _, g, theta, _ = wide
    pairs = csr.pairs_from_product(g.transpose())
    assert pairs.pair_butterflies0().max() > INT32_MAX
    assert csr.vertex_butterflies_csr(pairs).max() > INT32_MAX
    assert theta.max() > INT32_MAX


@pytest.mark.parametrize("fd_driver", ["device", "vmapped", "host"])
def test_wide_theta_and_forest_are_exact(wide, fd_driver):
    _, g, theta, forest = wide
    res = tip_decomposition(g, side="v", engine="csr", fd_driver=fd_driver)
    assert res.stats.count_bits == 64 and res.stats.pair_source == "mxu"
    assert np.array_equal(res.theta, theta)
    h = build_hierarchy(g, res, kind="tip", side="v")
    assert _forest_wrong(g, res, h, forest) == dict(theta_wrong=0,
                                                   forest_wrong=0)
    assert h.node_level.dtype == np.int64
    assert h.node_level.max() == theta.max()


def test_wide_theta_matches_the_bup_oracle(wide):
    name, g, theta, _ = wide
    assert np.array_equal(bup_tip_ref(g, side="v"), theta), name


def test_wide_timeline_rings_carry_64_bit_levels(wide):
    """Timeline mode runs the device driver's counter-ring twin; its k
    ring carries the supports' dtype, so levels past int32 survive."""
    _, g, theta, _ = wide
    obs.enable()
    try:
        res = tip_decomposition(g, side="v", engine="csr")
    finally:
        obs.disable()
    assert np.array_equal(res.theta, theta)
    assert max(int(L["k"].max()) for L in res.timeline.fd) == theta.max()


def test_launch_peel_edges_tip_side_v(wide, tmp_path):
    """``launch/peel.py --edges … --kind tip --side v``: the tiled init's
    int64 supports feed the engine instead of being refused."""
    from repro.launch.peel import build_parser, run

    _, g, _, _ = wide
    path = tmp_path / "edges.tsv"
    np.savetxt(path, g.edges, fmt="%d")
    args = build_parser().parse_args(
        ["--edges", str(path), "--kind", "tip", "--side", "v",
         "--ingest-dir", str(tmp_path / "ingest")])
    out = run(args)
    gi = out["graph"]
    want = reference.tip_numbers(gi.n_u, gi.n_v, np.asarray(gi.edges), "v")
    assert np.array_equal(out["theta"], want)
    assert out["stats"]["count_bits"] == 64


def _graph500(scale: int) -> BipartiteGraph:
    from bench.generators import kronecker

    cfg = json.loads((ROOT / "bench/configs/graph500.json").read_text())
    cfg["scale"] = scale
    n_u, n_v, e = kronecker.edges(cfg)
    return BipartiteGraph.from_edges(n_u, n_v, e)


def _hub(scale_u: int) -> BipartiteGraph:
    from bench.generators import kronecker_rect

    cfg = json.loads((ROOT / "bench/configs/graph500_hub.json").read_text())
    cfg["scale_u"] = scale_u
    n_u, n_v, e = kronecker_rect.edges(cfg)
    return BipartiteGraph.from_edges(n_u, n_v, e)


@pytest.mark.parametrize("name,make,side", [
    ("graph500_scale7", lambda: _graph500(7), "u"),
    ("graph500_scale7", lambda: _graph500(7), "v"),
    ("hub_scale_u12", lambda: _hub(12), "v"),
])
def test_product_pairs_equal_wedge_pairs(name, make, side):
    g = make()
    gg = g if side == "u" else g.transpose()
    mxu = csr.pairs_from_product(gg)
    wed = csr.build_wedges(gg)
    for f in ("pair_a", "pair_b", "W0"):
        assert np.array_equal(getattr(mxu, f), getattr(wed, f)), (name, f)
    assert (mxu.source, mxu.dtype) == ("mxu", "int8")
    assert mxu.rows % 128 == 0 and mxu.rows >= gg.n_u and mxu.k >= gg.n_v
    results = []
    for pairs in (mxu, wed):
        stats = PeelStats(engine="csr", fd_driver="device", side=side)
        spec = build_peel_spec(g, "tip", stats, side=side, engine="csr",
                               wed=pairs)
        results.append(decompose(spec, 16, stats))
    a, b = results
    assert np.array_equal(a.theta, b.theta), name
    assert (a.stats.rho_cd, a.stats.rho_fd_max, a.stats.updates) == \
        (b.stats.rho_cd, b.stats.rho_fd_max, b.stats.updates)
    assert a.stats.pair_source == "mxu" and b.stats.pair_source == "wedges"


@pytest.mark.parametrize("n_u,n_v,wedges,source", [
    # the hub cell at scale_u 18: 512 × 2^18, 80,100,916 wedges
    (512, 2 ** 18, 80_100_916, "mxu"),
    # graph500.tip: 512 × 512, 136,861 wedges, cheaper as the product
    (512, 512, 136_861, "mxu"),
    # a small graph: the launches cost more than its few wedges
    (80, 50, 3_000, "wedges"),
    # a sparse side: 4,096² counts to read back for 48,597 wedges
    (4096, 4096, 48_597, "wedges"),
    # an operand over 512 MiB
    (8192, 2 ** 20, 10 ** 12, "wedges"),
    # a count matrix over 256 MiB
    (20_000, 2 ** 10, 10 ** 12, "wedges"),
])
def test_the_rule_reads_the_degrees(n_u, n_v, wedges, source):
    assert csr.pair_source(n_u, n_v, wedges) == source


def test_the_product_takes_edges_out_of_order():
    """A graph whose edges break the sorted order the scatter declares
    still gets the wedge list's pairs and counts."""
    g = _graph500(7)
    order = np.random.default_rng(5).permutation(g.m)
    got = csr.pairs_from_product(BipartiteGraph(g.n_u, g.n_v, g.edges[order]))
    want = csr.build_wedges(g)
    for f in ("pair_a", "pair_b", "W0"):
        assert np.array_equal(getattr(got, f), getattr(want, f)), f


def test_an_int32_graph_keeps_int32_counts_and_spans_its_pairs():
    g = _hub(12)
    tracer = obs.enable(timeline=False)
    try:
        res = tip_decomposition(g, side="v", engine="csr")
    finally:
        obs.disable()
    assert res.stats.count_bits == 32
    assert res.stats.pair_source == "mxu"
    (sp,) = [e for e in tracer.events if e["name"] == "tip.pairs"]
    assert sp["args"] == dict(source="mxu", rows=512, k=4096,
                              pairs=res.pairs.n_pairs, bits=32)
    assert (res.stats.pair_rows, res.stats.pair_k,
            res.stats.pair_dtype) == (512, 4096, "int8")
    want = reference.tip_numbers(g.n_u, g.n_v, g.edges, "v")
    assert np.array_equal(res.theta, want)
