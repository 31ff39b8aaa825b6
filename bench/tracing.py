"""Spans from the benchmark's own wrappers, the profiler's trace, and the
reduction from that trace to device numbers.

Spans wrap the calls into each layer at run start, by replacing the
module attribute the caller looks up (:meth:`Spans.wrap`).  Each span
ends on the host once its results are there or have been blocked on,
and is written into the profiler's trace as a ``TraceAnnotation`` named
``bench.<span>``, so host spans and device operations share one clock.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import importlib
import json
import os
import shutil
import tempfile
import time
from collections import defaultdict

PREFIX = "bench."
WINDOW_SPAN = "window"        # the traced window: one job, or a serving slice


class Spans:
    """Host spans ``(name, job, seconds)`` recorded by wrappers; ``job``
    is -1 during set-up; the window numbers its work from 0."""

    def __init__(self):
        self.records = []
        self.job = -1
        self._undo = []

    @contextlib.contextmanager
    def span(self, name: str):
        """Record the block as span name."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.records.append((name, self.job, time.perf_counter() - t0))

    def wrap(self, module: str, attr: str, name: str, block: str = "") -> None:
        """Time every call of ``module.attr`` as span ``name``.  ``block``
        is "out" to block on the returned arrays, "live" to block on every
        live array (for results held inside objects), "" when the result
        is already on the host."""
        import jax

        mod = importlib.import_module(module)
        fn = getattr(mod, attr)

        def wrapped(*a, **kw):
            """The wrapped call, timed."""
            with self.span(name):
                out = fn(*a, **kw)
                if block == "out":
                    jax.block_until_ready(out)
                elif block == "live":
                    for x in jax.live_arrays():
                        x.block_until_ready()
            return out

        setattr(mod, attr, wrapped)
        self._undo.append((mod, attr, fn))

    def restore(self) -> None:
        """Put the wrapped attributes back."""
        for mod, attr, fn in reversed(self._undo):
            setattr(mod, attr, fn)
        self._undo.clear()

    def per_job(self, name: str, jobs=None) -> list:
        """Seconds of span ``name`` summed within each job."""
        tot = defaultdict(float)
        for n, j, s in self.records:
            if n == name and (jobs is None or j in jobs):
                tot[j] += s
        return [tot[j] for j in sorted(tot)]

    def window(self, name: str) -> list:
        """Seconds of every span ``name`` recorded in the window."""
        return [s for n, j, s in self.records if n == name and j >= 0]


@contextlib.contextmanager
def profiled():
    """Profile the block; yields a dict that gets ``events`` (the
    perfetto trace's event list) once the block has ended."""
    import jax

    out = {}
    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, create_perfetto_trace=True,
                             profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        try:
            f = glob.glob(os.path.join(d, "**", "perfetto_trace.json.gz"),
                          recursive=True)
            if f:
                with gzip.open(f[0], "rt") as fh:
                    out["events"] = json.load(fh)["traceEvents"]
        finally:
            shutil.rmtree(d, ignore_errors=True)


# ------------------------------------------------------------ reduction
def _union(iv):
    """Merge (start, end) intervals."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_name(e: dict) -> str:
    """What a device operation does: its ``tf_op`` path (such as
    ``jit(wing_update_csr)/gather``) where the trace gives one, else the
    HLO instruction's name."""
    tf = e.get("args", {}).get("tf_op", "").rstrip(":")
    return tf or e["name"]


def reduce(events: list, top: int = 10) -> dict:
    """Device numbers of the traced window, from perfetto trace events.

    The window is the host span ``bench.window``.  Device operations are
    the complete events on the "XLA Ops" line of each ``/device:TPU:n``
    process; a ``while`` event spans the operations of its body, so it
    counts towards busy time and towards nothing else.  Busy time is the
    union of the operations' intervals inside the window, averaged over
    the devices that ran any.  Returns ``window_s``, ``busy_s``,
    ``scatter_s`` and ``gather_s`` (operations whose :func:`op_name`
    ends in a scatter or a gather), ``device_ops`` (most time, by
    :func:`op_name`) and ``idle_gaps`` (device-idle time inside the
    window by the ``bench.*`` host span that covered most of each gap)."""
    pname, tname = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pname[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            tname[(e["pid"], e["tid"])] = e["args"]["name"]
    spans, ops = [], defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        s, d = float(e["ts"]), float(e.get("dur", 0.0))
        p = pname.get(e["pid"], "")
        if p.startswith("/device:TPU:") and \
                tname.get((e["pid"], e.get("tid"))) == "XLA Ops":
            nest = e.get("args", {}).get("hlo_category") == "while"
            ops[e["pid"]].append((s, s + d, op_name(e), nest))
        elif e["name"].startswith(PREFIX):
            spans.append((s, s + d, e["name"][len(PREFIX):]))
    win = [(s, t) for s, t, n in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = min(s for s, _ in win), max(t for _, t in win)
    by_name = defaultdict(float)
    busy, gaps = [], defaultdict(float)
    kind = defaultdict(float)
    layers = [x for x in spans if x[2] != WINDOW_SPAN]
    for pid, lst in ops.items():
        clipped = [(max(s, w0), min(t, w1), n, nest) for s, t, n, nest in lst
                   if t > w0 and s < w1]
        if not clipped:
            continue
        for s, t, n, nest in clipped:
            if nest:
                continue
            by_name[n] += t - s
            last = n.rsplit("/", 1)[-1].lower()
            for k in ("scatter", "gather"):
                if k in last:
                    kind[k] += t - s
        u = _union([(s, t) for s, t, _, _ in clipped])
        busy.append(sum(t - s for s, t in u))
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps[_host_span(layers, g0, g1)] += g1 - g0
    n_dev = max(len(busy), 1)
    us = 1e-6
    return dict(
        window_s=(w1 - w0) * us,
        busy_s=sum(busy) / n_dev * us,
        scatter_s=kind["scatter"] / n_dev * us,
        gather_s=kind["gather"] / n_dev * us,
        device_ops=[[n, t / n_dev * us] for n, t in
                    sorted(by_name.items(), key=lambda x: -x[1])[:top]],
        idle_gaps=[[n, t / n_dev * us] for n, t in
                   sorted(gaps.items(), key=lambda x: -x[1])[:top]],
    )


def _host_span(spans, g0, g1) -> str:
    """The host span that covers the most of (g0, g1)."""
    best, key = "outside layer spans", (0.0, 0.0)
    for s, t, n in spans:
        cover = min(t, g1) - max(s, g0)
        if cover > 0 and (cover, s) > key:
            best, key = n, (cover, s)
    return best
