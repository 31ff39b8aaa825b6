"""Batch driver: back-to-back decomposition jobs, graph to θ to forest.

A job builds a fresh graph object from the run's edge array, decomposes
it through the csr engine (``wing_decomposition`` or
``tip_decomposition``) and builds the forest (``build_hierarchy``); its
results are on the host when it returns.  Set-up runs one job, which
compiles or loads every program the window runs.  The window starts
whole jobs until its seconds have passed and lets the last one finish;
``batch_s`` is the window's length over the jobs it completed.

Traffic keys: ``kind`` ("wing" | "tip"), ``side`` (tip).
"""
from __future__ import annotations

import time

from . import check, graphs, tracing

SPANS = (  # (module, attribute, span, how its results reach the host)
    ("repro.core.peel", "build_peel_spec", "init", "live"),
    ("repro.core.peelspec", "cd_loop", "cd", ""),
    ("repro.core.peelspec", "run_fd", "fd", ""),
    ("repro.hierarchy", "build_hierarchy", "build", ""),
)


def decompose(g, kind: str, side: str) -> tuple:
    """θ and the forest of graph g, the way ``launch/peel.py --engine
    csr`` decomposes it: csr engine, device FD, 16 partitions."""
    import repro.hierarchy as hier
    from repro.core import peel

    if kind == "wing":
        res = peel.wing_decomposition(g, engine="csr")
    else:
        res = peel.tip_decomposition(g, side=side, engine="csr")
    return res, hier.build_hierarchy(g, res, kind=kind, side=side)


class Driver:
    """One batch cell's run: set-up, window, check."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, log, root=None):
        self.kind = traffic["kind"]
        self.side = traffic.get("side", "u")
        self.log = log
        self.n_u, self.n_v, edges = graphs.structure(cfg)
        self.edges = graphs.relabel(edges, self.n_u, self.n_v, seed)
        self.outs = []
        self.spans = None

    def wrap(self, spans: tracing.Spans) -> None:
        """Time the layers' calls as spans."""
        self.spans = spans
        for mod, attr, name, block in SPANS:
            spans.wrap(mod, attr, name, block)

    def _job(self) -> dict:
        from repro.core.graph import BipartiteGraph

        g = BipartiteGraph.from_edges(self.n_u, self.n_v, self.edges)
        res, h = decompose(g, self.kind, self.side)
        return dict(edges=g.edges, theta=res.theta, stats=res.stats.as_dict(),
                    forest={f: getattr(h, f) for f in check.FOREST_FIELDS})

    def setup(self) -> None:
        """One warm job: every program the window runs is compiled or loaded."""
        from repro import obs

        obs.disable()
        t0 = time.perf_counter()
        self._job()
        self.log(f"warm job {time.perf_counter() - t0:.3f} s")

    def window(self, seconds: float, trace: bool) -> dict:
        """Run the window; returns the end-to-end numbers and, traced,
        the reduced trace of its first job."""
        reduced = None
        t0 = time.perf_counter()
        ends = []
        while True:
            j = len(self.outs)
            if self.spans is not None:
                self.spans.job = j
            if trace and j == 0:
                with tracing.profiled() as prof:
                    with self.spans.span(tracing.WINDOW_SPAN):
                        out = self._job()
                reduced = tracing.reduce(prof["events"])
            else:
                out = self._job()
            self.outs.append(out)
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds:
                break
        span = time.perf_counter() - t0
        n = len(self.outs)
        jobs = [round(b - a, 3) for a, b in zip([0.0] + ends, ends)]
        self.log(f"window {span:.3f} s, {n} jobs: {jobs}")
        return dict(values=dict(batch_s=span / n), attempted=n,
                    trace=reduced)

    def check(self) -> tuple:
        """(numbers compared, with their limits; jobs found wrong)."""
        per_job = check.compare_batch(self.kind, self.side, self.n_u,
                                      self.n_v, self.edges, self.outs)
        checks = {k: (sum(j[k] for j in per_job), 0)
                  for k in ("theta_wrong", "forest_wrong")}
        return checks, sum(any(j.values()) for j in per_job)

    def layer_context(self) -> dict:
        """What the per-layer readers read: spans and each job's counters,
        of the jobs after the profiled one where there are any."""
        jobs = list(range(len(self.outs)))
        jobs = jobs[1:] or jobs
        return dict(spans=self.spans, jobs=jobs,
                    stats=[self.outs[j]["stats"] for j in jobs])

