"""The reduction from a profiler trace to device numbers: busy time as a
union of intervals, idle share, scatter and gather shares, and the
breakdown; on hand-made events and on a trace recorded on a TPU v5e."""
import gzip
import json

import pytest

from conftest import ROOT

from bench import tracing

FIXTURE = ROOT / "bench" / "fixtures" / "tip_job_trace.json.gz"


def _meta(pid, name, threads):
    ev = [dict(ph="M", pid=pid, name="process_name", args=dict(name=name))]
    ev += [dict(ph="M", pid=pid, tid=t, name="thread_name",
                args=dict(name=n)) for t, n in threads.items()]
    return ev


def _op(ts, dur, tf_op, cat="custom fusion"):
    return dict(ph="X", pid=3, tid=3, ts=ts, dur=dur, name="fusion.1",
                args=dict(hlo_category=cat, tf_op=tf_op + ":"))


def _span(ts, dur, name):
    return dict(ph="X", pid=7, tid=1, ts=ts, dur=dur,
                name=tracing.PREFIX + name)


def _events():
    ev = _meta(3, "/device:TPU:0", {3: "XLA Ops", 2: "XLA Modules"})
    ev += _meta(7, "/host:CPU", {1: "python3"})
    ev += [
        _span(0, 100, "window"),
        _span(0, 40, "cd"),
        _span(40, 60, "build"),
        _op(-10, 20, "jit(f)/gather"),           # clipped to [0, 10)
        _op(5, 10, "jit(f)/scatter-add"),        # overlaps: union [0, 15)
        _op(30, 20, "jit(g)/while", cat="while"),  # spans its body
        _op(32, 10, "jit(g)/while/body/scatter-min"),
        _op(90, 30, "jit(h)/add"),               # clipped to [90, 100)
        dict(ph="X", pid=3, tid=2, ts=0, dur=100, name="jit_f(1)"),
    ]
    return ev


def test_union_merges_overlaps():
    assert tracing._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                                [5, 9]]


def test_reduce_on_hand_made_events():
    r = tracing.reduce(_events())
    us = 1e-6
    assert r["window_s"] == pytest.approx(100 * us)
    # busy: [0, 15) + [30, 50) + [90, 100)
    assert r["busy_s"] == pytest.approx(45 * us)
    assert r["scatter_s"] == pytest.approx(20 * us)     # 10 + 10
    assert r["gather_s"] == pytest.approx(10 * us)
    names = dict(r["device_ops"])
    assert "jit(g)/while" not in names                  # a container
    assert names["jit(f)/gather"] == pytest.approx(10 * us)
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1]
    gaps = dict(r["idle_gaps"])
    # idle: [15, 30) under cd, [50, 90) under build
    assert gaps == pytest.approx({"cd": 15 * us, "build": 40 * us})


def test_reduce_needs_a_window():
    with pytest.raises(ValueError):
        tracing.reduce([e for e in _events()
                        if e.get("name") != tracing.PREFIX + "window"])


def test_reduce_on_a_trace_recorded_on_the_chip():
    with gzip.open(FIXTURE, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    r = tracing.reduce(events)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["scatter_s"] + r["gather_s"] <= r["busy_s"]
    assert 1 <= len(r["device_ops"]) <= 10
    assert len(r["idle_gaps"]) <= 10
    secs = [s for _, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    idle = r["window_s"] - r["busy_s"]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(idle, rel=1e-6)
    assert {n for n, _ in r["idle_gaps"]} <= {"init", "cd", "fd", "build",
                                              "outside layer spans"}
