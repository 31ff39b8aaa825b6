"""The benchmark's plain reference agrees with the system at tiny sizes:
wing and tip numbers, the forest, and the five query answers; its
control (coarse peeling) does not."""
import numpy as np
import pytest

from bench import check, graphs, reference
from bench.generators import kronecker


def _graph(seed, scale=6, edgefactor=6):
    return kronecker.edges(dict(scale=scale, edgefactor=edgefactor, A=0.57,
                                B=0.19, C=0.19, graph_seed=seed))


def _system(kind, side, n_u, n_v, e):
    from repro.core.graph import BipartiteGraph
    from repro.core.peel import tip_decomposition, wing_decomposition
    from repro.hierarchy import build_hierarchy

    g = BipartiteGraph.from_edges(n_u, n_v, e)
    res = (wing_decomposition(g, engine="csr") if kind == "wing" else
           tip_decomposition(g, side=side, engine="csr"))
    h = build_hierarchy(g, res, kind=kind, side=side)
    return g, res, h


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_numbers_match_the_bup_oracle(seed):
    from repro.core import ref
    from repro.core.graph import BipartiteGraph

    n_u, n_v, e = _graph(seed)
    g = BipartiteGraph.from_edges(n_u, n_v, e)
    assert np.array_equal(reference.wing_numbers(n_u, n_v, e),
                          ref.bup_wing_ref(g))
    for side in "uv":
        assert np.array_equal(reference.tip_numbers(n_u, n_v, e, side),
                              ref.bup_tip_ref(g, side))


@pytest.mark.parametrize("kind,side", [("wing", "u"), ("tip", "u"),
                                       ("tip", "v")])
@pytest.mark.parametrize("seed", [3, 4])
def test_forest_and_answers_match_the_system(kind, side, seed):
    from repro.hierarchy import HierarchyService, HQuery, OPS

    n_u, n_v, e = _graph(seed)
    # a relabelled copy: the system sees other ids, the same graph
    e = graphs.relabel(e, n_u, n_v, seed + 2 ** 40)
    g, res, h = _system(kind, side, n_u, n_v, e)
    forest = check.reference_forest(kind, side, n_u, n_v, e)
    out = dict(edges=g.edges, theta=res.theta,
               forest={f: getattr(h, f) for f in check.FOREST_FIELDS})
    canon = reference.canonical_edges(e)
    assert check.compare_job(kind, out, canon, forest) == dict(
        theta_wrong=0, forest_wrong=0)

    rng = np.random.default_rng(seed)
    names = sorted(OPS, key=OPS.get)
    n = 500
    ops = rng.integers(0, len(names), n)
    a = rng.integers(0, h.n_entities, n)
    node = ops == OPS["subtree_size"]
    a[node] = rng.integers(0, h.n_nodes, int(node.sum()))
    b = rng.integers(0, h.n_entities, n)
    svc = HierarchyService(h, batch=128)
    for i in range(n):
        svc.submit(HQuery(uid=i, op=names[ops[i]], a=int(a[i]), b=int(b[i])))
    got = np.array([x.result for x in svc.run()])
    from bench.serve import _wrong_answers

    emap = check.entity_map(kind, g.edges, canon)
    cid = check.node_ids(out["forest"], emap, forest)
    assert _wrong_answers(forest, emap, cid, ops, a, b, got) == 0
    bad = got.copy()
    bad[0] += 1
    assert _wrong_answers(forest, emap, cid, ops, a, b, bad) == 1


@pytest.mark.parametrize("kind", ["wing", "tip"])
def test_the_control_is_caught(kind):
    n_u, n_v, e = _graph(5)
    exact = check.reference_forest(kind, "u", n_u, n_v, e)
    ctrl = check.reference_forest(kind, "u", n_u, n_v, e, coarse=2)
    canon = reference.canonical_edges(e)
    assert check.compare_job(kind, check.as_output(exact, e), canon,
                             exact) == dict(theta_wrong=0, forest_wrong=0)
    wrong = check.compare_job(kind, check.as_output(ctrl, e), canon, exact)
    assert wrong["theta_wrong"] > 0 and wrong["forest_wrong"] > 0
