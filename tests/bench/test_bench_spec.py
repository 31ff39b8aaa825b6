"""BENCHMARK.json and the files it names: each configuration, mix and
metric reader loads by name; a cell added by files alone is found; the
command refuses to run without a TPU or without the system under test."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bm():
    return spec.load(ROOT)


def test_every_config_mix_and_metric_loads_by_name(bm):
    for c in bm["configs"]:
        cfg = spec.config(bm, c["name"])
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg and key in cfg["published"]
            assert cfg[key] < cfg["published"][key]
    for w in bm["workloads"]:
        mix = spec.traffic(w["traffic"])
        assert mix["driver"] in ("batch", "serve")
        assert spec.config(bm, w["config"])
    for m in bm["per_layer"]:
        read = spec.reader(m["name"])
        assert read({}) is None          # finds nothing: reports nothing


def test_names_units_and_bounds_keep_to_the_contract(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bm[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bm["end_to_end"])
    e2e = {m["name"] for m in bm["end_to_end"]}
    for w in bm["workloads"]:
        assert w["chips"] == 1
        mine = {m["name"] for m in spec.metrics_for(bm, "end_to_end",
                                                    w["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        layer = spec.metrics_for(bm, "per_layer", w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in mine and m["moves"] in e2e
    for p in bm["paths"]:
        assert (ROOT / p).is_dir()
    texts = [x[k] for sec in ("configs", "workloads") for x in bm[sec]
             for k in ("why", "source") if k in x]
    texts += [m["layer"] for m in bm["per_layer"]] + bm["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_a_cell_added_by_files_alone_is_found(tiny_root):
    bm = spec.load(tiny_root)
    cell = spec.cell(bm, "tiny.wing")
    cfg = spec.config(bm, cell["config"], tiny_root)
    assert cfg["scale"] == 7 and cfg["generator"] == "kronecker"
    assert spec.traffic("tiny_serve", tiny_root)["rate_qps"] == 2000
    names = {m["name"] for m in spec.metrics_for(bm, "per_layer",
                                                 "tiny.serve")}
    assert "dispatch_ms" in names and "cd_ms" not in names
    with pytest.raises(spec.SpecError):
        spec.cell(bm, "tiny.nothing")


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(ROOT, "--workload", "graph500.wing", "--seed", "2147483650",
             "--seconds", "1", "--trace", "0")
    assert r.returncode == 3, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_refuses_without_the_system_under_test(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, "--workload", "graph500.wing", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
