"""Whole runs of tiny cells on the CPU, the harness's look for a chip
skipped: the result line's schema, the per-layer metrics of a traced
run, and ``correct`` coming out false when the timed path is broken
underneath (a number or a forest entry altered where the job makes it,
a served answer altered where the dispatch makes it), and under the
control (the reference's coarse peel in the system's place)."""
import json
import time

import numpy as np
import pytest

from bench import run

pytestmark = pytest.mark.usefixtures("jax_config_restored")


def _run(root, cell, trace=False, seconds=1.0, seed=2 ** 31 + 7):
    return run.run_cell(cell, seed, seconds, trace, root=root,
                        require_tpu=False, t0=time.perf_counter())


def _schema(out, e2e):
    assert list(out)[:3] == ["correct", "attempted", "failed"]
    assert list(out)[-1] == "checks"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert set(out["metrics"]) == set(e2e)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.loads(json.dumps(out))


def test_batch_cell_result_line(tiny_root):
    out = _run(tiny_root, "tiny.wing")
    _schema(out, {"setup_s", "batch_s"})
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert out["checks"]["theta_wrong"] == dict(value=0, limit=0)


def test_traced_batch_cell_reports_its_layers(tiny_root):
    out = _run(tiny_root, "tiny.tip", trace=True)
    assert out["correct"]
    names = set(out["metrics"])
    assert {"init_ms", "cd_ms", "cd_rounds", "fd_ms", "fd_rounds_max",
            "build_ms"} <= names
    assert "device_idle.batch" in names      # no device ops on the CPU
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


def test_serve_cell_result_line(tiny_root):
    out = _run(tiny_root, "tiny.serve")
    _schema(out, {"setup_s", "query_p50_ms", "query_p90_ms"})
    assert out["unbounded"]["query_p99_ms"]["value"] > 0
    assert out["correct"], out["checks"]
    assert out["checks"]["answers_missing"]["value"] == 0
    traced = _run(tiny_root, "tiny.serve", trace=True)
    assert traced["correct"]
    assert {"dispatch_ms", "queries_per_dispatch",
            "gen_late_ms"} <= set(traced["metrics"])


def _alter_theta(monkeypatch):
    from repro.core import peel

    real = peel.wing_decomposition

    def broken(*a, **kw):
        res = real(*a, **kw)
        res.theta[np.argmax(res.theta)] += 1
        return res

    monkeypatch.setattr(peel, "wing_decomposition", broken)


def _alter_forest(monkeypatch):
    import repro.hierarchy as hier

    real = hier.build_hierarchy

    def broken(*a, **kw):
        h = real(*a, **kw)
        h.parent[-1] = 0 if h.parent[-1] else 1
        return h

    monkeypatch.setattr(hier, "build_hierarchy", broken)


def _alter_answer(monkeypatch):
    from repro.hierarchy import multiserve

    real = multiserve._answer_batch_multi

    def broken(*a, **kw):
        return real(*a, **kw).at[0].add(1)

    monkeypatch.setattr(multiserve, "_answer_batch_multi", broken)


@pytest.mark.parametrize("cell,fault,caught_by", [
    ("tiny.wing", _alter_theta, "theta_wrong"),
    ("tiny.wing", _alter_forest, "forest_wrong"),
    ("tiny.serve", _alter_answer, "answers_wrong"),
])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                            fault, caught_by):
    fault(monkeypatch)
    out = _run(tiny_root, cell, seed=11)
    assert out["correct"] is False
    assert out["checks"][caught_by]["value"] > 0
    assert out["failed"] > 0


@pytest.mark.parametrize("cell", ["tiny.wing", "tiny.tip", "tiny.serve"])
def test_the_control_in_the_systems_place_is_not_correct(tiny_root,
                                                         monkeypatch, cell):
    from bench import control

    control.install(patch=monkeypatch.setattr)
    out = _run(tiny_root, cell, seed=13)
    assert out["correct"] is False
    assert out["checks"]["theta_wrong"]["value"] > 0
