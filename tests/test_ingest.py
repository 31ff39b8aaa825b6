"""Out-of-core ingestion + bounded-tile counting correctness.

Three contracts:

* **Chunk/order invariance** (hypothesis): the chunked streaming
  dedup + degree-ordered relabel must produce the bit-identical
  :class:`IngestedGraph` for ANY chunk size and ANY line order —
  presence is the sign-net of inserts/deletes, so duplicates,
  self-cancelling lines, isolated vertices and non-contiguous raw ids
  all reduce the same way.  A dict-based oracle defines the semantics.
* **Tiled ≡ untiled ⋈init**: ``csr.tiled_butterfly_init`` must be
  bit-identical to the flat wedge-list counts on the paper proxies,
  host and Pallas tile paths alike.
* **End-to-end golden**: the committed real dataset ingests, counts
  and peels to the θ checksums recorded in
  ``tests/goldens/real_graphs.json``.
"""
import hashlib
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import csr
from repro.core.graph import paper_proxy_dataset, powerlaw_bipartite
from repro.data.ingest import ingest_edges

HERE = os.path.dirname(os.path.abspath(__file__))
DATASET = os.path.join(HERE, "..", "datasets", "southern_women.tsv")


# ---------------------------------------------------------------- oracle
def _oracle(ops):
    """Reference semantics for a list of (u_raw, v_raw, sign) lines."""
    net = {}
    for u, v, s in ops:
        net[(u, v)] = net.get((u, v), 0) + s
    present = sorted(k for k, n in net.items() if n > 0)
    vocab_u = sorted({u for u, _, _ in ops})
    vocab_v = sorted({v for _, v, _ in ops})
    deg_u, deg_v = {}, {}
    for u, v in present:
        deg_u[u] = deg_u.get(u, 0) + 1
        deg_v[v] = deg_v.get(v, 0) + 1

    def ranks(vocab, deg):
        order = sorted(vocab, key=lambda r: (-deg.get(r, 0), r))
        return {r: i for i, r in enumerate(order) if deg.get(r, 0) > 0}

    ru, rv = ranks(vocab_u, deg_u), ranks(vocab_v, deg_v)
    edges = sorted((ru[u], rv[v]) for u, v in present)
    return edges, len(ru), len(rv)


def _write(path, ops, order=None, header=True):
    lines = [f"{u}\t{v}" if s > 0 else f"{u}\t{v}\t-1" for u, v, s in ops]
    if order is not None:
        lines = [lines[i] for i in order]
    with open(path, "w") as f:
        if header:
            f.write("% bip unweighted\n")
        f.write("\n".join(lines) + ("\n" if lines else ""))


def _assert_graph(ig, ops):
    edges, n_u, n_v = _oracle(ops)
    assert (ig.n_u, ig.n_v, ig.m) == (n_u, n_v, len(edges))
    got = [tuple(map(int, e)) for e in np.asarray(ig.edges)]
    assert got == edges
    du, dv = ig.degrees()
    # degree-ordered relabel: ranks are decreasing-degree on both sides
    assert all(du[i] >= du[i + 1] for i in range(n_u - 1))
    assert all(dv[i] >= dv[i + 1] for i in range(n_v - 1))
    # V-CSR view consistent with the edge list
    off, nbr, eid = ig.csr_v()
    assert np.array_equal(np.sort(eid), np.arange(ig.m))
    u_of = np.asarray(ig.edges)[:, 0]
    v_of = np.asarray(ig.edges)[:, 1]
    centers = np.repeat(np.arange(n_v), np.diff(off))
    assert np.array_equal(v_of[eid], centers)
    assert np.array_equal(u_of[eid], nbr)


# non-contiguous raw ids exercise the vocab compaction
def _raw(u, v):
    return 7 * u + 3, 1_000_000 + 13 * v


_OPS = st.lists(
    st.tuples(st.booleans(), st.integers(0, 9), st.integers(0, 7)),
    min_size=1, max_size=40)


@settings(max_examples=60, deadline=None)
@given(raw_ops=_OPS, rng=st.randoms(use_true_random=False))
def test_ingest_invariant_to_chunks_and_order(raw_ops, rng, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ing")
    ops = [(*_raw(u, v), 1 if ins else -1) for ins, u, v in raw_ops]
    p0 = str(tmp / "a.tsv")
    _write(p0, ops)
    ig0 = ingest_edges(p0, out_dir=str(tmp / "a.ing"))
    _assert_graph(ig0, ops)
    # chunk-size invariance, including chunk=1 (one edge resident)
    for ce in (1, 3):
        igc = ingest_edges(p0, out_dir=str(tmp / f"c{ce}.ing"),
                           chunk_edges=ce)
        assert np.array_equal(np.asarray(igc.edges), np.asarray(ig0.edges))
        assert (igc.n_u, igc.n_v, igc.m) == (ig0.n_u, ig0.n_v, ig0.m)
    # line-order invariance (net semantics are order-free)
    order = list(range(len(ops)))
    rng.shuffle(order)
    p1 = str(tmp / "b.tsv")
    _write(p1, ops, order=order)
    ig1 = ingest_edges(p1, out_dir=str(tmp / "b.ing"), chunk_edges=5)
    assert np.array_equal(np.asarray(ig1.edges), np.asarray(ig0.edges))
    assert (ig1.n_u, ig1.n_v, ig1.m) == (ig0.n_u, ig0.n_v, ig0.m)


def test_ingest_edge_cases(tmp_path):
    # self-cancelling pair + duplicate inserts + isolated-by-deletion
    ops = [(5, 100, 1), (5, 100, -1),       # cancels: u=5 isolated
           (7, 100, 1), (7, 100, 1),        # duplicate insert (net 2)
           (9, 200, 1)]
    p = str(tmp_path / "e.tsv")
    _write(p, ops)
    ig = ingest_edges(p, out_dir=str(tmp_path / "e.ing"))
    _assert_graph(ig, ops)
    assert ig.m == 2 and ig.n_u == 2  # raw u=5 dropped entirely
    assert ig.meta["n_dropped_u"] == 1

    # cache hit returns without re-ingesting; refresh rebuilds
    ig2 = ingest_edges(p, out_dir=str(tmp_path / "e.ing"))
    assert np.array_equal(np.asarray(ig2.edges), np.asarray(ig.edges))

    # everything cancels -> empty graph
    p0 = str(tmp_path / "z.tsv")
    _write(p0, [(1, 2, 1), (1, 2, -1)])
    igz = ingest_edges(p0, out_dir=str(tmp_path / "z.ing"))
    assert (igz.n_u, igz.n_v, igz.m) == (0, 0, 0)


# ------------------------------------------------- tiled ≡ untiled ⋈init
@pytest.mark.parametrize("tile_wedges,use_pallas,width", [
    (700, False, 512),
    (10 ** 9, False, 512),    # single tile == whole graph
    (2500, True, 64),         # Pallas rows, hub pairs split across rows
])
def test_tiled_init_bit_identical_fr(tile_wedges, use_pallas, width):
    g = paper_proxy_dataset("fr")
    w = csr.build_wedges(g)
    sup_e, sup_u, total, stats = csr.tiled_butterfly_init(
        g, tile_wedges=tile_wedges, use_pallas=use_pallas, width=width)
    assert np.array_equal(sup_e, csr.edge_butterflies0(w))
    assert np.array_equal(sup_u, csr.vertex_butterflies_csr(w))
    assert total == csr.total_butterflies_csr(w)
    assert stats.n_wedges == w.n_wedges
    assert stats.n_pairs == w.n_pairs
    if tile_wedges < w.n_wedges:
        assert stats.n_tiles > 1
        # the bounded-memory claim: peak ≈ tile budget, not Σ deg²
        assert stats.peak_tile_wedges < w.n_wedges


def test_tiled_init_peak_bounded_by_budget():
    g = powerlaw_bipartite(300, 200, 2400, seed=5)
    w = csr.build_wedges(g)
    per_u = np.zeros(g.n_u, dtype=np.int64)
    np.add.at(per_u, np.minimum(w.pair_a, w.pair_b)[w.wedge_pair], 1)
    budget = 512
    _, _, _, stats = csr.tiled_butterfly_init(g, tile_wedges=budget)
    # a tile only exceeds the budget via one hub vertex's own wedges
    assert stats.peak_tile_wedges <= budget + int(per_u.max())


# -------------------------------------------------- end-to-end real graph
def _sha(theta):
    return hashlib.sha256(
        np.asarray(theta, dtype=np.int64).tobytes()).hexdigest()


def test_real_graph_end_to_end_golden(tmp_path):
    from repro.core.peel import tip_decomposition, wing_decomposition

    with open(os.path.join(HERE, "goldens", "real_graphs.json")) as f:
        want = json.load(f)["southern_women"]
    ig = ingest_edges(DATASET, out_dir=str(tmp_path / "sw.ing"))
    assert (ig.n_u, ig.n_v, ig.m) == (want["n_u"], want["n_v"], want["m"])
    sup_e, sup_u, total, _ = csr.tiled_butterfly_init(ig, tile_wedges=64)
    assert total == want["total_butterflies"]
    g = ig.as_graph()
    wing = wing_decomposition(g, engine="csr", sup0=sup_e)
    assert _sha(wing.theta) == want["theta_wing_sha256"]
    tip = tip_decomposition(g, side="u", engine="csr", sup0=sup_u)
    assert _sha(tip.theta) == want["theta_tip_u_sha256"]
