"""Cross-tenant slot-batched hierarchy serving over a ForestPool.

:class:`MultiTenantService` is :class:`~repro.hierarchy.serve.HierarchyService`
lifted to many tenants: every queue entry carries ``(tenant, op, a, b)``,
the engine groups queued slots by the tenant's *shape bucket*, and ONE
jitted dispatch per bucket answers every tenant in it.  The kernel is
``serve._answer_batch`` extended with a leading tenant-gather — each
slot first selects its tenant's row of the bucket's stacked arrays,
then runs the same branchless answer-family select, so a mixed-tenant
mixed-op batch costs exactly one compiled program per bucket shape
(compile-count asserted in tests; answers are bit-identical to a
per-tenant ``HierarchyService``).

Cold tenants are loaded through the pool's LRU artifact cache at
submit time; loading cannot evict any tenant that still has queued
slots, so a batch can never be invalidated by its own admissions.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from .pool import BucketKey, ForestPool
from .serve import _OP_NAMES, OPS

__all__ = ["MTQuery", "MultiTenantService"]


@dataclasses.dataclass
class MTQuery:
    """One query against one tenant; ``result`` is filled by the engine."""

    uid: int
    tenant: str
    op: str
    a: int
    b: int = 0
    result: Optional[int] = None
    done: bool = False


def _lca_multi(up, depth, t, x, y, J: int):
    """Binary-lifting LCA with a leading tenant axis: identical algebra
    to ``query._lca``, every gather routed through tenant row ``t``."""
    dx = depth[t, x]
    dy = depth[t, y]
    swap = dy > dx
    a = jnp.where(swap, y, x)
    b = jnp.where(swap, x, y)
    diff = depth[t, a] - depth[t, b]
    for j in range(J):                     # lift a to b's depth
        a = jnp.where((diff >> j) & 1 > 0, up[t, a, j], a)
    eq = a == b
    for j in range(J - 1, -1, -1):         # descend to just below LCA
        ne = (up[t, a, j] != up[t, b, j]) & ~eq
        a = jnp.where(ne, up[t, a, j], a)
        b = jnp.where(ne, up[t, b, j], b)
    return jnp.where(eq, a, up[t, a, 0])


@partial(jax.jit, static_argnames=("J",))
def _answer_batch_multi(
    theta, entity_node, node_level, depth, node_size, up,
    tenant, ops, a, b, J: int,
):
    """``serve._answer_batch`` with a leading tenant-gather: arrays are
    (slots, …) stacks, ``tenant`` routes each query slot to its row.
    Same op table, same branchless select — the two kernels cannot
    desynchronize because both key through :data:`OPS` by name."""
    ea = entity_node[tenant, a]
    lca = _lca_multi(up, depth, tenant, ea, entity_node[tenant, b], J)
    answers = {
        "max_k": theta[tenant, a],
        "node_of": ea,
        "lca_node": lca,
        "lca_level": node_level[tenant, lca],
        "subtree_size": node_size[tenant, a],
    }
    assert answers.keys() == OPS.keys()
    return jnp.select(
        [ops == OPS[name] for name in answers],
        list(answers.values()),
        default=jnp.int32(-1),
    )


def _tenant_counts(tenants: Sequence[str]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for t in tenants:
        counts[t] = counts.get(t, 0) + 1
    return counts


def compiled_dispatch_count() -> int:
    """Number of compiled multi-tenant dispatch programs — one per
    (bucket shape, batch size) the service has seen.  The zero-retrace
    invariant is stated on this counter: cold-loading a tenant into an
    existing bucket must not change it."""
    return _answer_batch_multi._cache_size()


class MultiTenantService:
    """Slot-batched mixed-op serving across every tenant of a pool.

    ``batch`` is the slot count of each compiled dispatch; queued
    queries are grouped per shape bucket and padded with no-op slots,
    so one XLA program per bucket serves any query/tenant mix.

    Example::

        pool = ForestPool(slots=8, artifact_dir="/data/hierarchies")
        svc = MultiTenantService(pool, batch=256)
        svc.submit(MTQuery(uid=0, tenant="books", op="max_k", a=3))
        svc.submit(MTQuery(uid=1, tenant="games", op="lca_level", a=1, b=7))
        print([q.result for q in svc.run()])
    """

    def __init__(self, pool: ForestPool, batch: int = 1024):
        self.pool = pool
        self.batch = int(batch)
        self.queue: Deque[MTQuery] = deque()
        self.served = 0
        self.dispatches = 0
        # shares the pool's registry: one snapshot covers cache + serve
        self.metrics = pool.metrics

    # ------------------------------------------------------------ admin
    def _validate(self, tenant: str, op: str, a: int, b: int) -> None:
        """Bounds-check against the TENANT's true dims (not the padded
        bucket shape — jitted gathers clamp, so an id past the tenant's
        real range would otherwise read another tenant's padding and
        answer confidently wrong)."""
        if op not in OPS:
            raise ValueError(f"unknown op {op!r} (choose from {set(OPS)})")
        m = self.pool.meta[tenant]
        a_lim = m.n_nodes if op == "subtree_size" else m.n_entities
        bad = not 0 <= a < a_lim
        if op in ("lca_node", "lca_level"):
            bad |= not 0 <= b < m.n_entities
        if bad:
            raise ValueError(
                f"query id out of range: tenant={tenant} op={op} a={a} "
                f"b={b} (n_entities={m.n_entities}, n_nodes={m.n_nodes})"
            )

    def submit(self, q: MTQuery) -> None:
        """Queue one query; the tenant is ensured resident (cold load
        through the LRU cache) and protected from eviction until its
        batch retires."""
        self.pool.ensure(q.tenant)
        self._validate(q.tenant, q.op, q.a, q.b)
        self.pool.note_queued(q.tenant, +1)
        self.queue.append(q)
        self.metrics.set_gauge("serve.queue_depth", len(self.queue))

    def pending(self) -> int:
        """Number of queued queries not yet served by :meth:`run`."""
        return len(self.queue)

    # ------------------------------------------------------------ serve
    def query_batch(
        self, tenants: Sequence[str], ops: np.ndarray, a: np.ndarray,
        b: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Raw batched entry: parallel arrays of tenant ids, op codes
        and args → int32 answers.  Slots are grouped by shape bucket
        and each group dispatches in fixed ``batch``-slot chunks.  Used
        directly by benchmarks; :meth:`run` wraps it."""
        with obs.span("serve.query_batch", cat="serve") as sp:
            ops = np.asarray(ops, dtype=np.int32)
            a = np.asarray(a, dtype=np.int32)
            b = (np.zeros_like(a) if b is None
                 else np.asarray(b, dtype=np.int32))
            tenants = list(tenants)
            if not (len(tenants) == ops.size == a.size == b.size):
                raise ValueError("tenants/ops/a/b must be parallel arrays")
            distinct = list(dict.fromkeys(tenants))
            pinned: List[str] = []
            try:
                with obs.span("serve.admit", cat="serve"):
                    # pin every already-known tenant against eviction
                    # BEFORE any cold load: an admission mid-batch must
                    # not drop another tenant whose slots ride in this
                    # same batch
                    for t in distinct:
                        if t in self.pool.meta:
                            self.pool.note_queued(t, +1)
                            pinned.append(t)
                    for t in distinct:
                        self.pool.ensure(t)
                        if t not in pinned:
                            self.pool.note_queued(t, +1)
                            pinned.append(t)
                    for i, t in enumerate(tenants):
                        self._validate(t, _OP_NAMES[int(ops[i])], int(a[i]),
                                       int(b[i]))
                out = self._dispatch_grouped(tenants, ops, a, b)
                if sp is not None:
                    sp.update(n=len(tenants), buckets=len(
                        {self.pool.meta[t].bucket for t in distinct}))
                return out
            finally:
                for t in pinned:
                    self.pool.note_queued(t, -1)

    def _dispatch_grouped(self, tenants, ops, a, b) -> np.ndarray:
        """Group validated slots by bucket, pack each group into
        fixed-size padded chunks, dispatch the chunks, scatter answers
        back to slot order."""
        out = np.zeros(len(tenants), np.int32)
        chunks = []
        with obs.span("serve.pack", cat="serve"):
            groups: Dict[BucketKey, List[int]] = {}
            slot_of = {t: self.pool.meta[t].slot for t in set(tenants)}
            for i, t in enumerate(tenants):
                groups.setdefault(self.pool.meta[t].bucket, []).append(i)
            for key, idx in groups.items():
                for lo in range(0, len(idx), self.batch):
                    chunk = idx[lo:lo + self.batch]
                    # pad with subtree_size(node 0) on tenant-slot 0 — the
                    # root always exists for a resident tenant, and a free
                    # slot 0 is all zeros (answer 0, masked out anyway)
                    t_sl = np.zeros(self.batch, np.int32)
                    op_c = np.full(self.batch, OPS["subtree_size"], np.int32)
                    a_c = np.zeros(self.batch, np.int32)
                    b_c = np.zeros(self.batch, np.int32)
                    for j, i in enumerate(chunk):
                        t_sl[j] = slot_of[tenants[i]]
                        op_c[j] = ops[i]
                        a_c[j] = a[i]
                        b_c[j] = b[i]
                    chunks.append((key, chunk, (t_sl, op_c, a_c, b_c)))
        for key, chunk, slots in chunks:
            arrs = self.pool.bucket_arrays(key)
            J = self.buckets_J(key)
            n = len(chunk)
            t0 = time.perf_counter()
            with obs.span("serve.dispatch", cat="serve") as sp:
                res = _answer_batch_multi(
                    arrs["theta"], arrs["entity_node"],
                    arrs["node_level"], arrs["depth"],
                    arrs["node_size"], arrs["up"],
                    *(jnp.asarray(x) for x in slots), J,
                )
                out[chunk] = np.asarray(res)[:n]
                if sp is not None:
                    sp.update(bucket=list(key), n=n)
            self.metrics.observe("serve.dispatch_ms",
                                 (time.perf_counter() - t0) * 1e3)
            self.metrics.inc("serve.dispatches")
            self.metrics.inc("serve.slots_padded", self.batch - n)
            self.dispatches += 1
            self.served += n
        self.metrics.inc("serve.served", len(tenants))
        for t, cnt in _tenant_counts(tenants).items():
            self.metrics.inc(f"serve.tenant.{t}", cnt)
        return out

    def buckets_J(self, key: BucketKey) -> int:
        """The bucket's static binary-lifting depth (part of the
        compiled dispatch signature)."""
        return self.pool.buckets[key].J

    def run(self) -> List[MTQuery]:
        """Drain the queue; returns completed queries in uid order (the
        ContinuousBatcher contract, like ``HierarchyService.run``)."""
        todo = list(self.queue)
        self.queue.clear()
        self.metrics.set_gauge("serve.queue_depth", 0)
        if todo:
            res = self._dispatch_grouped(
                [q.tenant for q in todo],
                np.asarray([OPS[q.op] for q in todo], np.int32),
                np.asarray([q.a for q in todo], np.int32),
                np.asarray([q.b for q in todo], np.int32),
            )
            for q, r in zip(todo, res):
                q.result = int(r)
                q.done = True
                self.pool.note_queued(q.tenant, -1)
        return sorted(todo, key=lambda q: q.uid)
