"""Multi-tenant hierarchy serving driver.

Serves a directory of hierarchy artifacts (``<tenant>.npz``, written by
``launch/peel.py --emit-hierarchy`` / ``repro.hierarchy.save_hierarchy``)
behind one endpoint: tenants load through the pool's LRU artifact cache
into shape-bucketed slots, and mixed-tenant mixed-op query batches are
answered with ONE jitted dispatch per shape bucket
(``repro.hierarchy.multiserve``).

``--dryrun`` needs no artifacts: it synthesizes tenants in two shape
buckets, serves a mixed workload, and asserts the serving-layer
structural claims — exactly one compiled dispatch per bucket, a cold
same-bucket load triggering zero retraces, and a dispatch jaxpr that is
pure gathers/selects (no ``while``, no collectives).

The serve loop shuts down gracefully: SIGINT/SIGTERM stop it between
dispatch chunks, queued slots are drained, the final metrics snapshot
(``--metrics``) and trace (``--trace``) are flushed, and the process
exits 0.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time


class GracefulShutdown:
    """Flip ``stop`` on SIGINT/SIGTERM instead of dying mid-dispatch;
    previous handlers are restored on exit (nested use is safe)."""

    def __init__(self):
        self.stop = False
        self._prev = {}

    def _handler(self, signum, frame):
        self.stop = True

    def __enter__(self):
        for s in (signal.SIGINT, signal.SIGTERM):
            try:
                self._prev[s] = signal.signal(s, self._handler)
            except ValueError:      # not the main thread
                pass
        return self

    def __exit__(self, *exc):
        for s, h in self._prev.items():
            signal.signal(s, h)
        return False


def _mixed_workload(pool, tenants, n, seed=0):
    """Random mixed-op parallel arrays over ``tenants`` (round-robin),
    each slot's ids drawn inside its tenant's true dims."""
    import numpy as np

    from repro.hierarchy.serve import OPS

    rng = np.random.default_rng(seed)
    t_col = [tenants[i % len(tenants)] for i in range(n)]
    ops = rng.integers(0, 5, n).astype(np.int32)
    a = np.zeros(n, np.int32)
    b = np.zeros(n, np.int32)
    for i, t in enumerate(t_col):
        m = pool.meta[t]
        lim = m.n_nodes if ops[i] == OPS["subtree_size"] else m.n_entities
        a[i] = rng.integers(0, max(lim, 1))
        b[i] = rng.integers(0, max(m.n_entities, 1))
    return t_col, ops, a, b


def _dryrun() -> int:
    import os as _os
    _os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + _os.environ.get("XLA_FLAGS", ""))
    import tempfile

    import jax
    # virtual host devices: pin the CPU platform (see launch/peel.py)
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from repro.core.graph import powerlaw_bipartite
    from repro.core.peel import wing_decomposition
    from repro.hierarchy import (ForestPool, MultiTenantService,
                                 build_hierarchy, multiserve, save_hierarchy)

    d = tempfile.mkdtemp(prefix="hserve_dryrun_")
    shapes = [(120, 80, 420), (120, 80, 420), (120, 80, 420), (24, 16, 64)]
    for i, (nu, nv, m) in enumerate(shapes):
        g = powerlaw_bipartite(nu, nv, m, seed=i)
        h = build_hierarchy(g, wing_decomposition(g, P=4, engine="csr"))
        save_hierarchy(os.path.join(d, f"tenant{i}.npz"), h)

    pool = ForestPool(slots=8, artifact_dir=d)
    svc = MultiTenantService(pool, batch=256)
    warm = ["tenant0", "tenant1", "tenant3"]   # two shape buckets
    for t in warm:
        pool.ensure(t)
    tenants, ops, a, b = _mixed_workload(pool, warm, 1024)
    svc.query_batch(tenants, ops, a, b)
    n_buckets = len(pool.buckets)
    n_compiles = multiserve.compiled_dispatch_count()
    assert n_compiles == n_buckets, (n_compiles, n_buckets)
    print(f"[hserve-dryrun] {len(warm)} tenants over {n_buckets} shape "
          f"buckets: exactly ONE compiled dispatch per bucket ✓")

    # cold load into the big bucket: values change, shapes don't —
    # the dispatch cache must not grow
    pool.ensure("tenant2")
    tenants, ops, a, b = _mixed_workload(pool, warm + ["tenant2"], 1024)
    svc.query_batch(tenants, ops, a, b)
    assert multiserve.compiled_dispatch_count() == n_compiles, \
        "cold same-bucket load must not retrace"
    print("[hserve-dryrun] cold same-bucket tenant load: ZERO retraces ✓")

    # the dispatch program is pure gathers + selects: no while, no
    # collectives (it must stay latency-shaped at any device count —
    # lowered here on the 512-device host platform)
    key = pool.meta["tenant0"].bucket
    arrs = pool.bucket_arrays(key)
    z = jnp.zeros(256, jnp.int32)
    jaxpr = str(jax.make_jaxpr(
        lambda *x: multiserve._answer_batch_multi(
            *x, J=svc.buckets_J(key)))(
        arrs["theta"], arrs["entity_node"], arrs["node_level"],
        arrs["depth"], arrs["node_size"], arrs["up"], z, z, z, z))
    assert "while[" not in jaxpr, "dispatch must be loop-free"
    assert not any(c in jaxpr for c in ("psum", "all_gather", "ppermute")), \
        "dispatch must be collective-free"
    print(f"[hserve-dryrun] dispatch jaxpr is loop- and collective-free "
          f"({len(jax.devices())} host devices) ✓")

    # eviction safety: pin one tenant, flood the pool, assert survival
    pool.pin("tenant3")
    for i in range(4):
        g = powerlaw_bipartite(24, 16, 64, seed=100 + i)
        h = build_hierarchy(g, wing_decomposition(g, P=2, engine="csr"))
        save_hierarchy(os.path.join(d, f"flood{i}.npz"), h)
    small_pool = ForestPool(slots=2, artifact_dir=d)
    small_pool.pin("tenant3")
    for i in range(4):
        small_pool.ensure(f"flood{i}")
    assert small_pool.resident("tenant3"), "pinned tenant must survive"
    print("[hserve-dryrun] pinned tenant survives a pool flood ✓")
    return 0


def _run(args) -> int:
    import numpy as np

    from repro import obs
    from repro.hierarchy import ForestPool, MultiTenantService, multiserve

    tenants = sorted(
        f[:-4] for f in os.listdir(args.artifact_dir) if f.endswith(".npz"))
    if not tenants:
        print(f"[hserve] no *.npz artifacts in {args.artifact_dir}")
        return 1
    pool = ForestPool(slots=args.pool_slots, artifact_dir=args.artifact_dir)
    svc = MultiTenantService(pool, batch=args.batch)
    warm = tenants[:args.pool_slots]
    t0 = time.perf_counter()
    with obs.span("serve.warm", cat="serve", n=len(warm)):
        for t in warm:
            pool.ensure(t)
    t_load = time.perf_counter() - t0
    print(f"[hserve] {len(tenants)} tenants found; warmed {len(warm)} "
          f"into {len(pool.buckets)} shape buckets in {t_load * 1e3:.1f} ms")

    served = 0
    checksum = np.int64(0)
    interrupted = False
    # the shutdown handler covers workload generation too: a SIGINT any
    # time after the warm print takes the graceful path
    with GracefulShutdown() as gs:
        t_col, ops, a, b = _mixed_workload(pool, warm, args.queries,
                                           seed=args.seed)
        t0 = time.perf_counter()
        try:
            # one dispatch-sized chunk per iteration so a shutdown
            # signal is honored between dispatches, never inside one
            for lo in range(0, args.queries, args.batch):
                if gs.stop:
                    interrupted = True
                    break
                hi = min(lo + args.batch, args.queries)
                out = svc.query_batch(
                    t_col[lo:hi], ops[lo:hi], a[lo:hi], b[lo:hi])
                checksum += np.int64(out.sum())
                served += hi - lo
        finally:
            # drain queued slots so no tenant retires with in-flight
            # queries (run() is a no-op on an empty queue)
            svc.run()
        dt = time.perf_counter() - t0
        interrupted = interrupted or gs.stop
    qps = served / max(dt, 1e-9)
    print(f"[hserve] {served} mixed-tenant queries in "
          f"{dt * 1e3:.1f} ms -> {qps:,.0f} q/s "
          f"({svc.dispatches} dispatches, "
          f"{multiserve.compiled_dispatch_count()} compiled programs)")
    print(f"[hserve] cache: {pool.stats()}")
    if interrupted:
        print("[hserve] shutdown signal: queue drained, telemetry "
              "flushed, exiting 0")
    svc.metrics.set_gauge("serve.qps", qps)
    if args.metrics:
        svc.metrics.save(args.metrics)
        print(f"[hserve] metrics snapshot -> {args.metrics}")
    if args.out:
        import json
        with open(args.out, "w") as f:
            json.dump(dict(qps=qps, n_tenants=len(warm),
                           served=served,
                           answers_checksum=int(checksum),
                           **pool.stats()), f)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifact-dir", default=None, metavar="DIR",
                    help="directory of <tenant>.npz hierarchy artifacts "
                         "(write them with launch/peel.py "
                         "--emit-hierarchy)")
    ap.add_argument("--pool-slots", type=int, default=64,
                    help="resident-tenant budget of the forest pool "
                         "(LRU eviction past it)")
    ap.add_argument("--batch", type=int, default=1024,
                    help="slots per compiled dispatch")
    ap.add_argument("--queries", type=int, default=50_000,
                    help="size of the mixed-op probe workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="dump qps + cache stats JSON")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write the final serving-metrics snapshot "
                         "(pool.* cache counters, serve.* dispatch "
                         "latency histograms with p50/p99) as JSON")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable the observability layer and write a "
                         "Chrome-trace JSON of the serve run (warm / "
                         "cold-load / dispatch spans; open in Perfetto)")
    ap.add_argument("--dryrun", action="store_true",
                    help="no artifacts needed: synthesize two shape "
                         "buckets and assert the serving invariants "
                         "(one compile per bucket, zero-retrace cold "
                         "load, loop/collective-free dispatch)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        from repro import obs
        obs.enable()
    if args.dryrun:
        rc = _dryrun()
    else:
        if not args.artifact_dir:
            ap.error("--artifact-dir is required (or pass --dryrun)")
        rc = _run(args)
    if args.trace:
        from repro import obs
        tracer = obs.get_tracer()
        tracer.save(args.trace)
        print(f"[hserve] trace: {len(tracer.events)} events -> "
              f"{args.trace}")
    sys.exit(rc)


if __name__ == "__main__":
    main()
