"""Graph canonicalisation: ``BipartiteGraph``'s packed-key orders against
the row-sort formulations they replace.

``from_edges`` dedups and sorts by one int64 key ``u·n_v + v``;
``transpose`` sorts ``v·n_u + u``; ``csr_u``/``csr_v`` stable-argsort
the key.  Each must give the arrays the row sorts give —
``np.unique(axis=0)`` and two-column ``np.lexsort`` — bit for bit.
"""
import numpy as np
import pytest

from repro import obs
from repro.core.graph import BipartiteGraph

TOP = 2**31 - 1


# ------------------------------------------------ the replaced formulations
def _ref_from_edges(n_u, n_v, edges):
    e = np.asarray(edges, dtype=np.int32).reshape(-1, 2)
    if e.size:
        e = np.unique(e, axis=0)
        assert e[:, 0].min() >= 0 and e[:, 0].max() < n_u, "u id out of range"
        assert e[:, 1].min() >= 0 and e[:, 1].max() < n_v, "v id out of range"
    return e


def _ref_transpose(edges):
    e = edges[:, ::-1].copy()
    return e[np.lexsort((e[:, 1], e[:, 0]))]


def _ref_csr(edges, n, major):
    minor = 1 - major
    order = np.lexsort((edges[:, minor], edges[:, major]))
    off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(edges[:, major], minlength=n), out=off[1:])
    return off, edges[order, minor].astype(np.int32), order.astype(np.int32)


# ------------------------------------------------------------------ inputs
def _random(n_u, n_v, m, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.integers(0, n_u, m), rng.integers(0, n_v, m)], 1)


def _sorted_rows():
    return np.unique(_random(30, 20, 200, 1), axis=0)


CASES = {
    "many_duplicates": lambda: (12, 9, _random(12, 9, 500, 2)),
    "sparse_random": lambda: (300, 200, _random(300, 200, 900, 3)),
    "already_sorted": lambda: (30, 20, _sorted_rows()),
    "reverse_order": lambda: (30, 20, _sorted_rows()[::-1]),
    "empty": lambda: (5, 7, np.zeros((0, 2), np.int64)),
    "empty_list": lambda: (5, 7, []),
    "single_edge": lambda: (4, 6, np.array([[3, 5]])),
    "int64_input": lambda: (40, 50, _random(40, 50, 300, 4).astype(np.int64)),
    "list_input": lambda: (8, 8, _random(8, 8, 40, 5).tolist()),
    "flat_input": lambda: (8, 8, _random(8, 8, 40, 6).ravel()),
    "one_column_of_v": lambda: (50, 1, _random(50, 1, 80, 7)),
    "one_row_of_u": lambda: (1, 50, _random(1, 50, 80, 8)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_from_edges_matches_row_unique(case):
    n_u, n_v, edges = CASES[case]()
    g = BipartiteGraph.from_edges(n_u, n_v, edges)
    want = _ref_from_edges(n_u, n_v, edges)
    assert g.edges.dtype == np.int32
    assert g.edges.shape == want.shape and g.edges.ndim == 2
    assert g.edges.flags.c_contiguous
    assert np.array_equal(g.edges, want)
    assert (g.n_u, g.n_v) == (n_u, n_v)


@pytest.mark.parametrize("case", sorted(CASES))
def test_transpose_and_csr_match_lexsort(case):
    n_u, n_v, edges = CASES[case]()
    g = BipartiteGraph.from_edges(n_u, n_v, edges)
    t = g.transpose()
    assert (t.n_u, t.n_v) == (n_v, n_u)
    assert t.edges.dtype == np.int32 and t.edges.flags.c_contiguous
    assert np.array_equal(t.edges, _ref_transpose(g.edges))
    for got, want in ((g.csr_u(), _ref_csr(g.edges, n_u, 0)),
                      (g.csr_v(), _ref_csr(g.edges, n_v, 1))):
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_direct_graph_with_duplicate_unsorted_rows_keeps_tie_order(seed):
    """A graph built without ``from_edges`` keeps its duplicate rows in
    ``transpose`` and gets lexsort's tie order from ``csr_u``/``csr_v``:
    the stable argsort pins the same edge ids."""
    n_u, n_v = 6, 5
    e = _random(n_u, n_v, 120, 10 + seed).astype(np.int32)
    g = BipartiteGraph(n_u, n_v, e)
    assert np.array_equal(g.transpose().edges, _ref_transpose(e))
    for got, want in ((g.csr_u(), _ref_csr(e, n_u, 0)),
                      (g.csr_v(), _ref_csr(e, n_v, 1))):
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_ids_at_the_top_of_int32():
    """The largest key, (2**31-2)·(2**31-1) + 2**31-2, still fits int64."""
    edges = np.array([[TOP - 1, TOP - 1], [0, TOP - 1], [TOP - 1, 0],
                      [TOP - 1, TOP - 1], [5, 7], [TOP - 2, TOP - 1], [0, 0]],
                     dtype=np.int64)
    g = BipartiteGraph.from_edges(TOP, TOP, edges)
    want = _ref_from_edges(TOP, TOP, edges)
    assert g.edges.dtype == np.int32 and np.array_equal(g.edges, want)
    t = g.transpose()
    assert np.array_equal(t.edges, _ref_transpose(want))


BAD = {
    "u_too_large": (4, 4, [[0, 1], [4, 0]], "u id out of range"),
    "v_too_large": (4, 4, [[0, 1], [2, 4]], "v id out of range"),
    "negative_u": (4, 4, [[-1, 1], [2, 3]], "u id out of range"),
    "negative_v": (4, 4, [[1, -1], [2, 3]], "v id out of range"),
    # v = n_v would alias into (u + 1, 0) in the key if unchecked
    "v_aliases_next_row": (4, 3, [[0, 3], [1, 0]], "v id out of range"),
}


@pytest.mark.parametrize("case", sorted(BAD))
def test_out_of_range_ids_raise(case):
    n_u, n_v, edges, msg = BAD[case]
    with pytest.raises(AssertionError, match=msg):
        BipartiteGraph.from_edges(n_u, n_v, edges)
    with pytest.raises(AssertionError, match=msg):
        _ref_from_edges(n_u, n_v, edges)


def test_from_edges_span_counts_rows_in_and_out():
    edges = np.array([[1, 2], [0, 1], [1, 2], [0, 1], [2, 0]])
    obs.enable(timeline=False)
    try:
        g = BipartiteGraph.from_edges(3, 3, edges)
        (sp,) = [e for e in obs.get_tracer().events
                 if e["name"] == "graph.from_edges"]
    finally:
        obs.disable()
    assert sp["cat"] == "graph"
    assert sp["args"] == {"m_in": 5, "m_out": 3} and g.m == 3
