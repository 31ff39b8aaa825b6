"""Serving driver: open-loop forest queries through ``MultiTenantService``.

Tenants are forests of the configuration's graph (and of cuts of it),
built through the system's own path (csr peel, ``build_hierarchy``,
``save_hierarchy``) into artifacts under ``<checkout>/.bench_artifacts``
and loaded by a ``ForestPool`` from there, as ``launch/hserve.py``
serves them.  A later run of the cell finds the artifacts and skips the
build.

Arrivals are Poisson at ``rate_qps``; each query's tenant is drawn
Zipfian (YCSB, ``zipf_theta``) over the tenants in the order the mix
lists them, its op uniformly over the five ops, its ids uniformly
within the tenant's forest.  A generator thread releases queries at
their due times; the serving loop hands every released query, up to
``batch`` at a time, to ``MultiTenantService.query_batch``.  A query's
latency runs from its due time to its answer on the host, so a stall
shows up as the wait of every query behind it.  Queries due in the
window are all served, up to ``drain_s`` past its close.
"""
from __future__ import annotations

import hashlib
import json
import threading
import time
from pathlib import Path

import numpy as np

from . import batch, check, graphs, reference, tracing


class Driver:
    """The serving cell's run: set-up, window, check."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, log, root: Path):
        self.cfg, self.traffic, self.seed, self.log = cfg, traffic, seed, log
        self.artifacts = Path(root) / ".bench_artifacts"
        self.rate = float(traffic["rate_qps"])
        self.spans = None
        self.tenants = [f"{cfg['name']}.{t['name']}" for t in traffic["tenants"]]

    def wrap(self, spans: tracing.Spans) -> None:
        """Time every serving dispatch as a span."""
        self.spans = spans
        spans.wrap("repro.hierarchy.multiserve", "_answer_batch_multi",
                   "dispatch", "out")

    # ------------------------------------------------------------ set-up
    def _tenant_graph(self, t: dict) -> tuple:
        return graphs.structure(self.cfg, t["cut"])

    def _artifact_dir(self) -> Path:
        key = json.dumps([self.cfg, self.traffic["tenants"]], sort_keys=True)
        return self.artifacts / hashlib.sha256(key.encode()).hexdigest()[:16]

    def _build(self, d: Path) -> None:
        """Each tenant's forest through the system's path, saved."""
        from repro.core.graph import BipartiteGraph
        from repro.hierarchy import save_hierarchy

        d.mkdir(parents=True, exist_ok=True)
        for name, t in zip(self.tenants, self.traffic["tenants"]):
            path = d / f"{name}.npz"
            if path.exists():
                continue
            n_u, n_v, e = self._tenant_graph(t)
            g = BipartiteGraph.from_edges(n_u, n_v, e)
            _, h = batch.decompose(g, t["kind"], t.get("side", "u"))
            tmp = d / f"{name}.tmp"
            save_hierarchy(str(tmp), h)
            tmp.rename(path)
            self.log(f"built tenant {name}: {h.n_nodes} nodes")

    def _queries(self, seconds: float) -> None:
        """The window's arrivals, tenants, ops and ids, from the seed."""
        from repro.hierarchy.serve import OPS

        rng = np.random.default_rng(self.seed % 2 ** 64)
        n_max = int(self.rate * seconds * 1.2 + 100)
        due = np.cumsum(rng.exponential(1.0 / self.rate, n_max))
        due = due[due < seconds]
        n = due.size
        k = len(self.tenants)
        w = 1.0 / np.arange(1, k + 1) ** self.traffic["zipf_theta"]
        tidx = rng.choice(k, size=n, p=w / w.sum())
        ops = rng.integers(0, len(OPS), n).astype(np.int32)
        meta = [self.pool.meta[t] for t in self.tenants]
        n_ent = np.array([m.n_entities for m in meta])
        n_node = np.array([m.n_nodes for m in meta])
        lim = np.where(ops == OPS["subtree_size"], n_node[tidx], n_ent[tidx])
        a = (rng.random(n) * lim).astype(np.int32)
        b = (rng.random(n) * n_ent[tidx]).astype(np.int32)
        self.q = dict(due=due, tidx=tidx, ops=ops, a=a, b=b)

    def setup(self) -> None:
        """Build or load the tenants, admit them, compile each bucket's dispatch."""
        from repro import obs
        from repro.hierarchy import ForestPool, MultiTenantService

        obs.disable()
        d = self._artifact_dir()
        t0 = time.perf_counter()
        self._build(d)
        self.log(f"tenants on disk after {time.perf_counter() - t0:.3f} s")
        t = self.traffic
        self.pool = ForestPool(slots=t["pool_slots"], artifact_dir=str(d))
        self.svc = MultiTenantService(self.pool, batch=t["batch"])
        for name in self.tenants:
            self.pool.ensure(name)
        # every bucket's dispatch program, at the one batch shape
        for name in self.tenants:
            self.svc.query_batch([name] * t["batch"],
                                 np.zeros(t["batch"], np.int32),
                                 np.zeros(t["batch"], np.int32))
        self.log(f"{len(self.tenants)} tenants in "
                 f"{len(self.pool.buckets)} shape buckets")

    # ------------------------------------------------------------ window
    def window(self, seconds: float, trace: bool) -> dict:
        """Serve the window's queries; returns the end-to-end numbers."""
        self._queries(seconds)
        q = self.q
        n = q["due"].size
        names = np.array(self.tenants, dtype=object)[q["tidx"]]
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ans = np.zeros(n, np.int64)
        released = [0]
        stop = threading.Event()
        self.dispatches0 = self.svc.dispatches
        if self.spans is not None:
            self.spans.job = 0
        t0 = time.perf_counter()

        def generate():
            """Release every query at its due time."""
            i = 0
            while i < n and not stop.is_set():
                now = time.perf_counter() - t0
                j = int(np.searchsorted(q["due"], now, side="right"))
                if j > i:
                    self.sent[i:j] = now
                    released[0] = j
                    i = j
                else:
                    time.sleep(min(q["due"][i] - now, 5e-4))

        gen = threading.Thread(target=generate, daemon=True)
        gen.start()
        served = 0
        batch = self.traffic["batch"]
        deadline = seconds + self.traffic["drain_s"]
        reduced = None

        def serve_until(t_end):
            """Serve released queries until t_end (seconds into the window)."""
            nonlocal served
            while served < n:
                now = time.perf_counter() - t0
                if now >= t_end:
                    return
                r = released[0]
                if r == served:
                    time.sleep(2e-4)
                    continue
                hi = min(r, served + batch)
                out = self.svc.query_batch(
                    names[served:hi].tolist(), q["ops"][served:hi],
                    q["a"][served:hi], q["b"][served:hi])
                self.done[served:hi] = time.perf_counter() - t0
                self.ans[served:hi] = out
                served = hi

        try:
            if trace:
                # the window's last seconds: stopping the profiler and
                # reading its trace then delay no query due in the window
                serve_until(max(seconds - self.traffic["trace_seconds"], 0))
                with tracing.profiled() as prof:
                    with self.spans.span(tracing.WINDOW_SPAN):
                        serve_until(seconds)
            serve_until(deadline)
        finally:
            stop.set()
            gen.join(timeout=10)
        if trace:
            reduced = tracing.reduce(prof["events"])
        ok = ~np.isnan(self.done)
        lat = (self.done - q["due"])[ok] * 1e3
        late = (self.sent - q["due"])[~np.isnan(self.sent)] * 1e3
        self.missing = int(n - ok.sum())
        self.gen_late_p99 = float(np.percentile(late, 99)) if late.size else None
        self.log(f"window: {n} queries at {self.rate:g}/s, served "
                 f"{int(ok.sum())}, last answer at "
                 f"{np.nanmax(self.done) if ok.any() else 0:.3f} s, "
                 f"{self.svc.dispatches} dispatches so far")
        values = {f"query_p{q}_ms": float(np.percentile(lat, q))
                  for q in (50, 90)}
        # the machine's pauses of about 0.1 s every few seconds set the
        # 99th percentile, so it is reported without a bound
        p99 = dict(value=float(np.percentile(lat, 99)), unit="ms")
        return dict(values=values, attempted=n, trace=reduced,
                    unbounded=dict(query_p99_ms=p99))

    # ------------------------------------------------------------- check
    def check(self) -> tuple:
        """Every answer of the window against the reference, and every
        tenant's forest against the reference forest."""
        from repro.core.graph import BipartiteGraph
        from repro.hierarchy import load_hierarchy

        q = self.q
        d = self._artifact_dir()
        wrong_ans = 0
        wrong_forest = wrong_theta = 0
        answered = ~np.isnan(self.done)
        for i, (name, t) in enumerate(zip(self.tenants,
                                          self.traffic["tenants"])):
            n_u, n_v, e = self._tenant_graph(t)
            side = t.get("side", "u")
            forest = check.reference_forest(t["kind"], side, n_u, n_v, e)
            h = load_hierarchy(str(d / f"{name}.npz"))
            prog = dict(edges=BipartiteGraph.from_edges(n_u, n_v, e).edges,
                        theta=h.theta,
                        forest={f: getattr(h, f)
                                for f in check.FOREST_FIELDS})
            canon = reference.canonical_edges(e)
            got = check.compare_job(t["kind"], prog, canon, forest)
            wrong_theta += got["theta_wrong"]
            wrong_forest += got["forest_wrong"]
            sel = (q["tidx"] == i) & answered
            emap = check.entity_map(t["kind"], prog["edges"], canon)
            cid = check.node_ids(prog["forest"], emap, forest)
            wrong_ans += _wrong_answers(forest, emap, cid, q["ops"][sel],
                                        q["a"][sel], q["b"][sel],
                                        self.ans[sel])
        checks = dict(answers_wrong=(wrong_ans, 0),
                      answers_missing=(self.missing, 0),
                      theta_wrong=(wrong_theta, 0),
                      forest_wrong=(wrong_forest, 0))
        return checks, wrong_ans + self.missing

    def layer_context(self) -> dict:
        """What the per-layer readers read."""
        return dict(spans=self.spans, gen_late_p99_ms=self.gen_late_p99,
                    served=int((~np.isnan(self.done)).sum()),
                    dispatches=self.svc.dispatches - self.dispatches0)


def _wrong_answers(forest, emap, cid, ops, a, b, got) -> int:
    """Answers that differ from the reference's, node ids compared in
    the reference's numbering."""
    from repro.hierarchy.serve import OPS

    if ops.size == 0:
        return 0
    node_op = OPS["subtree_size"]
    ent = ops != node_op
    if emap is None:
        ca, cb = a.astype(np.int64), b.astype(np.int64)
    else:
        ca, cb = emap[a], emap[b]
    ca = np.where(ent, ca, cid[np.where(ent, 0, a)])
    bad = (ca < 0) | (cb < 0)
    ref_ops = np.array([reference.OPS.index(name) for name in
                        sorted(OPS, key=OPS.get)])[ops]
    want = reference.answer(forest, ref_ops, np.maximum(ca, 0),
                            np.maximum(cb, 0))
    node_ans = np.isin(ops, [OPS["node_of"], OPS["lca_node"]])
    got = np.asarray(got, np.int64)
    in_range = (got >= 0) & (got < cid.size)
    got_node = np.where(in_range, cid[np.clip(got, 0, cid.size - 1)], -2)
    got_c = np.where(node_ans, got_node, got)
    return int((bad | (got_c != want)).sum())
