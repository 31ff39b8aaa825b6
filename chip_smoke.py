#!/usr/bin/env python3
"""Smoke run of the PBNG batch path on a TPU, through the entry points a
user calls: graph → CD → FD → θ (``launch/peel.py``), then θ → forest →
batched queries (``repro.hierarchy``).

Phases, all in this one process (the chip belongs to one process):

(a) oracle, small — wing and tip (side u) on the ``fr`` proxy through
    every csr FD path the launcher takes on a TPU (``--fd-driver device``
    and ``vmapped``, each with and without ``--use-pallas``), θ against
    the BUP oracle (``core/ref.py``); the real ``southern_women`` edge
    list through the tiled ⋈init with ``--use-pallas``, θ against
    ``tests/goldens/real_graphs.json``.  Also asserts that no Pallas
    kernel runs in interpret mode, that every jitted Pallas entry point
    those runs called compiles, at the shapes it was called with, to a
    Mosaic kernel (``tpu_custom_call``), and that ``--fused-fd`` is
    refused.
(b) full size — ``--kind wing|tip --engine csr`` with the launcher's
    default flags on the seeded skewed ``peel`` graph of
    ``tests/goldens/record_chip_smoke.py``; prints |E|, the wedge count,
    device bytes, compile and run seconds, and checks each θ sha256
    against the CPU golden (``tests/goldens/chip_smoke.json``).
(c) forest and queries — on the smaller ``forest`` graph (at phase
    (b)'s size the wing build does not fit the time (a) and (b) leave;
    see the recorder): peel, ``build_hierarchy`` → ``pack_forest`` →
    4096 mixed ``HQuery`` through ``HierarchyService`` for wing and tip;
    θ and the forest digest against the CPU golden, every answer
    against a host oracle on that forest.

``--chips 4`` runs only the distributed csr paths (``--aligned``, wing
and tip) on a 4-chip mesh and the single-chip peel of the same
(``forest``) graph on one of those chips; θ must be bit-identical (and
equal the CPU golden), and every sharded CD input the distributed run
placed must have a shard on each of the 4 devices.

Usage, from the repo root on a machine with a TPU::

    python chip_smoke.py              # phases (a)-(c), one chip
    python chip_smoke.py --chips 4    # the four-chip mesh paths only

Exits non-zero before any phase when JAX finds no TPU.  The last line
of standard output is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
GOLDENS = os.path.join(ROOT, "tests", "goldens")
SOUTHERN_WOMEN = os.path.join(ROOT, "datasets", "southern_women.tsv")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, GOLDENS)

import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402
from record_chip_smoke import (KINDS, forest_digest,  # noqa: E402
                               launcher_args, theta_sha, v_centred_wedges)

# csr FD paths the launcher can take on one chip (--fused-fd is refused)
FD_PATHS = [("device", False), ("device", True),
            ("vmapped", False), ("vmapped", True)]
# the jitted entry points through which --use-pallas reaches a kernel
PALLAS_ENTRIES = {
    "wing CD support_update": ("repro.kernels.ops", "support_update"),
    "tip CD tip_slot_loss": ("repro.kernels.ops", "tip_slot_loss"),
    "tiled init tile_row_counts": ("repro.kernels.ops",
                                   "_tile_row_counts_inner"),
    "wing vmapped FD": ("repro.core.peel", "_fd_wing_vmapped_pallas"),
}
N_QUERIES = 4096

_COMPILE = dict(s=0.0, hits=0)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"[smoke] ok: {what}", flush=True)


def _count_compiles() -> None:
    """Sum JAX's own trace + lowering + XLA-compile durations (a
    persistent-cache hit makes the last one short) and count cache
    hits, so each phase can report compile apart from run time."""
    import jax

    def on_duration(event, duration_secs, **_):
        if event in _COMPILE_EVENTS:
            _COMPILE["s"] += duration_secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def _launch(argv):
    from repro.launch import peel

    return peel.run(peel.build_parser().parse_args(argv))


def _golden() -> dict:
    with open(os.path.join(GOLDENS, "chip_smoke.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- (a)
class _Recorder:
    """Stands in for a jitted entry point: runs it unchanged and keeps
    the abstract signature (shapes, dtypes, static arguments) of every
    call made outside a trace, so the program each call ran can be
    compiled again and read."""

    def __init__(self, fn):
        self.fn, self.calls = fn, {}

    def __call__(self, *args, **kw):
        import jax

        if not any(isinstance(x, jax.core.Tracer)
                   for x in jax.tree.leaves((args, kw))):
            sig = jax.tree.map(
                lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                           if hasattr(x, "shape") else x), (args, kw))
            self.calls.setdefault(repr(sig), sig)
        return self.fn(*args, **kw)


@contextlib.contextmanager
def _recording_pallas():
    recs = {}
    for what, (mod, attr) in PALLAS_ENTRIES.items():
        m = importlib.import_module(mod)
        recs[what] = (m, attr, _Recorder(getattr(m, attr)))
        setattr(m, attr, recs[what][2])
    try:
        yield {what: r for what, (_, _, r) in recs.items()}
    finally:
        for m, attr, r in recs.values():
            setattr(m, attr, r.fn)


def phase_a() -> None:
    from repro.core import ref
    from repro.core.graph import paper_proxy_dataset
    from repro.kernels import ops as kops
    from repro.launch.peel import LaunchError

    check(kops.default_interpret() is False,
          "Pallas kernels compile for the chip (interpret mode off)")
    g = paper_proxy_dataset("fr")
    oracle = dict(wing=ref.bup_wing_ref(g), tip=ref.bup_tip_ref(g, "u"))
    with open(os.path.join(GOLDENS, "real_graphs.json")) as f:
        sw = json.load(f)["southern_women"]
    key = dict(wing="theta_wing_sha256", tip="theta_tip_u_sha256")
    with _recording_pallas() as recs:
        for kind in KINDS:
            for driver, pallas in FD_PATHS:
                argv = ["--kind", kind, "--engine", "csr", "--dataset", "fr",
                        "--side", "u", "--fd-driver", driver]
                argv += ["--use-pallas"] if pallas else []
                r = _launch(argv)
                check(np.array_equal(r["theta"], oracle[kind]),
                      f"fr {kind} fd-driver={driver} use-pallas={pallas}: "
                      f"θ == BUP oracle")
        for kind in KINDS:
            r = _launch(["--kind", kind, "--engine", "csr", "--side", "u",
                         "--edges", SOUTHERN_WOMEN, "--use-pallas"])
            check(theta_sha(r["theta"]) == sw[key[kind]],
                  f"southern_women {kind} (tiled init, use-pallas): θ "
                  f"sha256 == golden")
    for what, rec in recs.items():
        check(len(rec.calls) > 0, f"--use-pallas called the {what}")
        for args, kw in rec.calls.values():
            shapes = [tuple(x.shape) for x in args if hasattr(x, "shape")]
            txt = rec.fn.lower(*args, **kw).compile().as_text()
            check("tpu_custom_call" in txt,
                  f"{what} at {shapes}: the program it ran holds a Mosaic "
                  f"kernel (tpu_custom_call)")
    try:
        _launch(["--kind", "wing", "--engine", "csr", "--dataset", "fr",
                 "--fused-fd"])
        refused = None
    except LaunchError as e:
        refused = str(e)
    check(refused is not None and "Mosaic" in refused,
          f"--fused-fd refused on the chip: {refused}")


# ---------------------------------------------------------------- (b)
def phase_b(golden: dict) -> None:
    import jax

    for kind in KINDS:
        c0, h0 = _COMPILE["s"], _COMPILE["hits"]
        t0 = time.perf_counter()
        r = _launch(launcher_args(kind, golden["graph"]))
        wall = time.perf_counter() - t0
        comp = _COMPILE["s"] - c0
        ms = jax.devices()[0].memory_stats() or {}
        g = r["graph"]
        print(f"[smoke] (b) {kind} |E|={g.m}")
        print(f"[smoke] (b) {kind} wedges={v_centred_wedges(g)}")
        print(f"[smoke] (b) {kind} device bytes_in_use="
              f"{ms.get('bytes_in_use')} "
              f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}")
        print(f"[smoke] (b) {kind} compile_s={comp:.3f} "
              f"cache_hits={_COMPILE['hits'] - h0}")
        print(f"[smoke] (b) {kind} run_s={wall - comp:.3f} "
              f"(wall {wall:.3f}, host graph+wedge build included)",
              flush=True)
        check(g.m == golden["m"], f"(b) {kind} |E| == golden")
        check(theta_sha(r["theta"]) == golden[kind]["theta_sha256"],
              f"(b) {kind} θ sha256 == CPU golden")


# ---------------------------------------------------------------- (c)
def _mixed_queries(h, n: int, seed: int = 0) -> tuple:
    from repro.hierarchy import OPS

    rng = np.random.default_rng(seed)
    names = sorted(OPS, key=OPS.get)
    ops = rng.integers(0, len(names), n)
    a = rng.integers(0, h.n_entities, n)
    node_arg = ops == OPS["subtree_size"]
    a[node_arg] = rng.integers(0, h.n_nodes, int(node_arg.sum()))
    b = rng.integers(0, h.n_entities, n)
    return [names[o] for o in ops], a, b


def _oracle_answers(h, ops, a, b) -> np.ndarray:
    """Host answers from the forest's parent array: LCA by walking up."""
    parent = h.parent
    depth = np.zeros(h.n_nodes, dtype=np.int64)
    for x in range(1, h.n_nodes):                # parent[x] < x
        depth[x] = depth[parent[x]] + 1

    def lca(x, y):
        while depth[x] > depth[y]:
            x = parent[x]
        while depth[y] > depth[x]:
            y = parent[y]
        while x != y:
            x, y = parent[x], parent[y]
        return x

    out = []
    for op, x, y in zip(ops, a, b):
        if op == "max_k":
            out.append(h.theta[x])
        elif op == "node_of":
            out.append(h.entity_node[x])
        elif op == "subtree_size":
            out.append(h.eend[x] - h.estart[x])
        else:
            z = lca(h.entity_node[x], h.entity_node[y])
            out.append(z if op == "lca_node" else h.node_level[z])
    return np.asarray(out, dtype=np.int64)


def phase_c(golden: dict) -> None:
    from repro.hierarchy import (HierarchyService, HQuery, build_hierarchy,
                                 pack_forest)

    for kind in KINDS:
        r = _launch(launcher_args(kind, golden["graph"]))
        check(theta_sha(r["theta"]) == golden[kind]["theta_sha256"],
              f"(c) {kind} θ sha256 == CPU golden")
        c0 = _COMPILE["s"]
        t0 = time.perf_counter()
        h = build_hierarchy(r["graph"], r["result"], kind=kind, side="u")
        t_build = time.perf_counter() - t0
        print(f"[smoke] (c) {kind} forest: {h.n_nodes} nodes over "
              f"{h.levels.size} levels, build {t_build:.3f} s (compile "
              f"{_COMPILE['s'] - c0:.3f} s)", flush=True)
        check(forest_digest(h) == golden[kind]["forest"],
              f"(c) {kind} forest digest == CPU forest")
        svc = HierarchyService(pack_forest(h), batch=1024)
        ops, a, b = _mixed_queries(h, N_QUERIES)
        for i, (op, x, y) in enumerate(zip(ops, a, b)):
            svc.submit(HQuery(uid=i, op=op, a=int(x), b=int(y)))
        t0 = time.perf_counter()
        got = np.asarray([q.result for q in svc.run()], dtype=np.int64)
        t_q = time.perf_counter() - t0
        print(f"[smoke] (c) {kind} {N_QUERIES} mixed queries in "
              f"{svc.dispatches} dispatches, {t_q:.3f} s", flush=True)
        check(np.array_equal(got, _oracle_answers(h, ops, a, b)),
              f"(c) {kind} {N_QUERIES} answers == host oracle on the "
              f"CPU forest")


# ------------------------------------------------------------ 4 chips
def phase_4chip(golden: dict) -> None:
    import jax

    from repro.core.peel import tip_decomposition, wing_decomposition

    n_dev = len(jax.devices())
    for kind in KINDS:
        t0 = time.perf_counter()
        r = _launch(launcher_args(kind, golden["graph"]) + ["--aligned"])
        print(f"[smoke] 4chip {kind} distributed over "
              f"{r['stats']['n_dev']} devices: "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        check(r["stats"]["n_dev"] == n_dev,
              f"4chip {kind} ran on the {n_dev}-device mesh")
        for name, where in r["stats"]["cd_shards"].items():
            print(f"[smoke] 4chip {kind} CD input {name}: shards "
                  f"{tuple(where['shard_shape'])} on devices "
                  f"{where['devices']}")
            check(len(set(where["devices"])) == n_dev,
                  f"4chip {kind} CD input {name} has a shard on each of "
                  f"the {n_dev} devices")
        g = r["graph"]
        t0 = time.perf_counter()
        with jax.default_device(jax.devices()[0]):
            one = (wing_decomposition(g, engine="csr") if kind == "wing"
                   else tip_decomposition(g, side="u", engine="csr"))
        print(f"[smoke] 4chip {kind} single chip: "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        check(np.array_equal(r["theta"], one.theta),
              f"4chip {kind} θ bit-identical to the single-chip peel")
        check(theta_sha(r["theta"]) == golden[kind]["theta_sha256"],
              f"4chip {kind} θ sha256 == CPU golden")


# ---------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c) on one chip; 4: only the "
                         "distributed csr paths on a 4-chip mesh against "
                         "the single-chip peel")
    args = ap.parse_args()

    enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) — nothing run",
              file=sys.stderr)
        return 2
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))
    print(f"[smoke] jax {jax.__version__} on {device}", flush=True)
    _count_compiles()

    golden = _golden()
    failed = []

    def run_phase(name, fn, *a):
        c0 = _COMPILE["s"]
        t0 = time.perf_counter()
        print(f"[smoke] phase {name} ...", flush=True)
        try:
            out = fn(*a)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            out = None
        print(f"[smoke] phase {name} {'FAILED' if name in failed else 'passed'}"
              f" in {time.perf_counter() - t0:.3f} s (compile "
              f"{_COMPILE['s'] - c0:.3f} s)", flush=True)
        return out

    if args.chips == 4:
        run_phase("4chip", phase_4chip, golden["forest"])
    else:
        run_phase("(a)", phase_a)
        run_phase("(b)", phase_b, golden["peel"])
        run_phase("(c)", phase_c, golden["forest"])
    if failed:
        print(f"[smoke] FAILED phases: {failed}", flush=True)
        return 1
    print(json.dumps(dict(ok=True, device=device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
