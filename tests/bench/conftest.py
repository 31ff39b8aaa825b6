"""Fixtures of the benchmark's tests: the repository root on
``sys.path`` (for the ``bench`` package), and a temporary benchmark
tree with a tiny configuration and its cells, added by files alone."""
import copy
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = dict(scale=7)
TINY_CELLS = {"tiny.wing": "wing", "tiny.tip": "tip",
              "tiny.serve": "tiny_serve"}


def make_tiny_root(dst: Path) -> Path:
    """A copy of the benchmark's files plus a ``tiny`` configuration and
    three cells on it, written as files and entries only."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench/configs/graph500.json").read_text())
    cfg.update(TINY, name="tiny")
    (dst / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/serve.json").read_text())
    mix.update(rate_qps=2000, trace_seconds=0.5)
    (dst / "bench/traffic/tiny_serve.json").write_text(json.dumps(mix))
    bm = copy.deepcopy(bm)
    bm["configs"].append(dict(name="tiny", source="a test graph",
                              file="bench/configs/tiny.json",
                              reduced=["scale"], why="tests"))
    for cell, mix_name in TINY_CELLS.items():
        bm["workloads"].append(dict(name=cell, config="tiny",
                                    traffic=mix_name, chips=1, why="tests"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        w = m.get("workloads")
        if w is not None:
            kinds = {x.split(".")[1] for x in w}
            w += [c for c in TINY_CELLS if c.split(".")[1] in kinds]
    (dst / "BENCHMARK.json").write_text(json.dumps(bm, indent=1))
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_tree"))


@pytest.fixture
def jax_config_restored():
    """run_cell turns the persistent compilation cache on; put JAX's
    settings back for the tests that follow in this process."""
    import jax

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
