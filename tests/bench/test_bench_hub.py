"""The ``graph500_hub.tip_v`` cell: its generator reproduces the
configuration's figures, a whole run at ``scale_u`` 10 comes out
correct on the CPU and takes the MXU pair source, and a support wrapped
at int32 makes it not correct."""
import json
import shutil
import time

import numpy as np
import pytest

from conftest import ROOT

from bench import graphs, run, spec

CELL = "graph500_hub.tip_v"


@pytest.fixture(scope="module")
def hub_root(tmp_path_factory):
    """The benchmark's files with ``graph500_hub`` cut to scale_u 10."""
    dst = tmp_path_factory.mktemp("hub_tree")
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = dst / "bench" / "configs" / "graph500_hub.json"
    cfg = json.loads(path.read_text())
    cfg["scale_u"] = 10
    path.write_text(json.dumps(cfg))
    return dst


def _run(root, seed, trace=False):
    return run.run_cell(CELL, seed, 1.0, trace, root=root,
                        require_tpu=False, t0=time.perf_counter())


def test_the_generator_gives_the_configurations_shape():
    cfg = spec.config(spec.load(ROOT), "graph500_hub")
    n_u, n_v, e = graphs.structure(cfg, cut=2.0 ** -8)
    assert (n_u, n_v) == (1024, 512)
    assert e.shape == (10_851, 2) and np.unique(e, axis=0).shape == e.shape
    du = np.bincount(e[:, 0], minlength=n_u)
    assert int((du * (du - 1) // 2).sum()) == 274_891
    assert e[:, 1].max() < n_v


@pytest.mark.usefixtures("jax_config_restored")
def test_a_hub_run_is_correct_through_the_product(hub_root, monkeypatch):
    from repro.core import csr

    sources = []
    real = csr.tip_pairs

    def spy(g):
        p = real(g)
        sources.append(p.source)
        return p

    monkeypatch.setattr(csr, "tip_pairs", spy)
    out = _run(hub_root, seed=2 ** 33 + 17)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert out["checks"]["theta_wrong"] == dict(value=0, limit=0)
    assert out["checks"]["forest_wrong"] == dict(value=0, limit=0)
    assert set(sources) == {"mxu"}
    assert len(sources) == 1 + out["attempted"]   # the build reuses it


@pytest.mark.usefixtures("jax_config_restored")
def test_a_support_wrapped_at_int32_is_not_correct(hub_root, monkeypatch):
    """The largest ⋈init reads 2^32 less, as an int32 register would
    read a support in [2^31, 2^32)."""
    from repro.core import csr

    real = csr.vertex_butterflies_csr

    def wrapped(w, *a, **kw):
        sup = real(w, *a, **kw)
        sup[np.argmax(sup)] -= 2 ** 32
        return sup

    monkeypatch.setattr(csr, "vertex_butterflies_csr", wrapped)
    out = _run(hub_root, seed=19)
    assert out["correct"] is False
    assert out["checks"]["theta_wrong"]["value"] > 0
    assert out["failed"] > 0
