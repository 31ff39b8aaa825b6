"""Graph-peeling service driver + production-mesh dry-run for PBNG.

This is the paper's analytic as a deployable job: load/generate a
bipartite graph, run distributed two-phase peeling over a device mesh,
emit wing/tip numbers + stats.  Flags are uniform across
``--kind wing`` and ``--kind tip`` (``--engine csr``, ``--aligned``,
``--fd-driver vmapped``, ``--use-pallas``); unsupported combinations are
rejected with an explicit error — never a silent fallback to another
engine.  ``--dryrun`` lowers the CD rounds and the FD partition-peels of
BOTH entity kinds on the 512-device production mesh and verifies the
structural claims (one-psum aligned CD, collective-free FD,
single-``while`` vmapped Phase 2) at scale.
"""
from __future__ import annotations

import argparse
import json
import sys


class LaunchError(SystemExit):
    """Unsupported flag combination — raised instead of silently
    falling back to a different engine/driver."""

    def __init__(self, msg: str):
        super().__init__(f"[peel] error: {msg}")


def _validate(args, n_dev: int) -> None:
    """Resolve the per-kind engine default, then reject unsupported
    flag combinations with explicit errors."""
    if args.engine is None:
        # per-kind default: the user never chose an engine, so resolve
        # to each kind's canonical one instead of erroring on a default
        # (real graphs default to csr — the only engine whose memory is
        # wedge-bounded, matching the tiled ⋈init they arrive through)
        if args.edges:
            args.engine = "csr"
        else:
            args.engine = "beindex" if args.kind == "wing" else "csr"
    if args.edges and args.dataset:
        raise LaunchError(
            "--edges and --dataset are exclusive graph sources")
    if args.edges and n_dev > 1:
        raise LaunchError(
            "--edges feeds the tiled ⋈init into the single-device "
            "engines; the distributed CD/FD paths take proxy graphs "
            "(run single-device, or --dryrun for mesh checks)")
    if args.kind == "tip" and args.engine == "beindex":
        raise LaunchError(
            "tip peels vertices — there is no BE-Index tip engine; "
            "pass --engine csr (scalable) or --engine dense")
    if args.use_pallas and args.engine != "csr":
        raise LaunchError(
            "--use-pallas routes csr slot layouts through the blocked "
            "kernels; pass --engine csr")
    if args.fd_driver == "vmapped" and args.engine != "csr":
        raise LaunchError(
            "--fd-driver vmapped is the csr single-dispatch Phase 2; "
            "pass --engine csr")
    if args.aligned and args.engine not in ("csr", "beindex"):
        raise LaunchError(
            "--aligned is the one-psum CD sharding (csr: pair/vertex "
            "aligned; beindex: bloom aligned); --engine dense has no "
            "sharded index to align")
    if n_dev > 1:
        if args.kind == "wing" and args.engine == "dense":
            raise LaunchError(
                "no distributed dense wing path; pass --engine "
                "beindex|csr (or run single-device)")
        if args.kind == "wing" and args.fd_driver == "vmapped":
            raise LaunchError(
                "distributed wing FD runs one while_loop per partition "
                "under shard_map (driver 'device'); the single-dispatch "
                "vmapped Phase 2 is single-device wing or distributed "
                "tip only")
        if args.fd_driver == "host":
            raise LaunchError(
                "--fd-driver host is the single-device A/B baseline; "
                "the distributed FD drivers are device|vmapped")
        if args.use_pallas:
            raise LaunchError(
                "--use-pallas is wired for the single-device csr "
                "engines; the distributed CD rounds use segment_sum "
                "shards")
    else:
        if args.aligned:
            raise LaunchError(
                "--aligned shards the CD index across devices; it needs "
                "a multi-device mesh (or use --dryrun)")


def _dryrun() -> int:
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    # the 512 devices are virtual host devices: pin the CPU platform so a
    # machine with an accelerator still builds the host mesh
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import distributed as D
    from repro.core.beindex import build_beindex
    from repro.core.graph import powerlaw_bipartite
    from repro.core.peel import wing_decomposition
    from repro.launch.mesh import make_peel_mesh

    mesh = make_peel_mesh(512)
    g = powerlaw_bipartite(400, 200, 2000, seed=1)
    be = build_beindex(g)

    # --- CD round at 512 devices
    st = D.shard_links(be, g.m, 512)
    fn = D.make_cd_round(mesh, "peel", st.nb, g.m)
    peeled = jnp.zeros((g.m + 1,), bool)
    sup = jnp.concatenate([st.support, jnp.zeros((1,), jnp.int32)])
    lowered = fn.lower(peeled, st.alive_link, st.k_alive, sup,
                       st.le, st.lt, st.lb)
    comp = lowered.compile()
    txt = comp.as_text()
    n_ar = txt.count("all-reduce")
    print(f"[peel-dryrun] CD round compiled at 512 devices; "
          f"all-reduce sites={n_ar}")

    # --- FD partition peel at 512 devices
    res = wing_decomposition(g, P=64, engine="beindex", be=be)
    packed = D.pack_fd_partitions(
        g, be, res.part, res.support_init, res.stats.p_effective,
    )
    n_parts = packed["le"].shape[0]
    pad = (-n_parts) % 512

    def padp(x):
        if pad == 0:
            return jnp.asarray(x)
        fill = np.zeros((pad,) + x.shape[1:], dtype=x.dtype)
        return jnp.asarray(np.concatenate([x, fill], 0))

    args_ = tuple(padp(packed[k]) for k in
                  ("le", "lt", "lb", "alive0", "canon", "k0", "sup0",
                   "mine"))
    vb = jax.vmap(D._fd_body_one_partition)
    fd = shard_map(vb, mesh=mesh,
                   in_specs=tuple(P("peel") for _ in args_),
                   out_specs=(P("peel"), P("peel")))
    fd_comp = jax.jit(fd).lower(*args_).compile()
    fd_txt = fd_comp.as_text()
    bad = [w for w in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")
           if w in fd_txt]
    assert not bad, f"FD must be collective-free, found {bad}"
    print("[peel-dryrun] FD peel compiled at 512 devices; "
          "NO collectives in HLO ✓")
    ca = fd_comp.cost_analysis() or {}
    print(f"[peel-dryrun] FD flops/device={ca.get('flops', -1):.3e} "
          f"bytes={ca.get('bytes accessed', -1):.3e}")

    # --- csr engine at 512 devices: wedge-sharded CD + wedge-packed FD
    from repro.core import csr

    wed = csr.build_wedges(g)
    st = D.shard_wedges(wed, 512)
    cfn = D.make_cd_round_csr(mesh, "peel", st.n_pairs, g.m)
    sup = jnp.concatenate([st.support, jnp.zeros((1,), jnp.int32)])
    ctxt = cfn.lower(peeled, st.alive_w, st.W_pad, sup,
                     st.we1, st.we2, st.wp).compile().as_text()
    print(f"[peel-dryrun] csr CD round compiled at 512 devices; "
          f"all-reduce sites={ctxt.count('all-reduce')}")

    # --- pair-aligned csr CD at 512 devices: ONE psum per round
    pal = D.shard_wedges_pair_aligned(wed, 512)
    pfn = D.make_cd_round_csr_pair_aligned(mesh, "peel", pal["Pmax"], g.m)
    ptxt = pfn.lower(peeled, jnp.asarray(pal["alive"]),
                     jnp.asarray(pal["W0"]), sup,
                     jnp.asarray(pal["we1"]), jnp.asarray(pal["we2"]),
                     jnp.asarray(pal["wp"])).compile().as_text()
    n_pal = ptxt.count("all-reduce(") + ptxt.count("all-reduce-start(")
    assert n_pal == 1, f"pair-aligned CD must pay ONE psum, found {n_pal}"
    print("[peel-dryrun] pair-aligned csr CD compiled at 512 devices; "
          "exactly ONE all-reduce per round ✓")

    res_c = wing_decomposition(g, P=64, engine="csr")
    packed_c = D.pack_fd_partitions_csr(
        wed, res_c.part, res_c.support_init, res_c.stats.p_effective)
    n_parts_c = packed_c["we1"].shape[0]
    pad_c = (-n_parts_c) % 512

    def padc(x):
        if pad_c == 0:
            return jnp.asarray(x)
        fill = np.zeros((pad_c,) + x.shape[1:], dtype=x.dtype)
        return jnp.asarray(np.concatenate([x, fill], 0))

    args_c = tuple(padc(packed_c[k]) for k in
                   ("we1", "we2", "wp", "alive0", "W0", "sup0", "mine"))
    fd_c = shard_map(jax.vmap(D._fd_body_one_partition_csr), mesh=mesh,
                     in_specs=tuple(P("peel") for _ in args_c),
                     out_specs=(P("peel"), P("peel")))
    fd_c_txt = jax.jit(fd_c).lower(*args_c).compile().as_text()
    bad_c = [w for w in ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute")
             if w in fd_c_txt]
    assert not bad_c, f"csr FD must be collective-free, found {bad_c}"
    print("[peel-dryrun] csr FD peel compiled at 512 devices; "
          "NO collectives in HLO ✓")

    # --- single-dispatch vmapped FD (single device): the whole Phase 2
    # must lower to exactly ONE while_loop with zero collectives
    from repro.core.peel import _fd_tip_vmapped, _fd_wing_vmapped

    packed_v = D.pack_fd_partitions_csr(
        wed, res_c.part, res_c.support_init, res_c.stats.p_effective,
        bucket=True, flat=True)
    args_v = tuple(jnp.asarray(packed_v[k]) for k in
                   ("flat_we1", "flat_we2", "flat_wp", "flat_alive0",
                    "flat_W0", "mine", "sup0"))
    n_pairs_v = int(packed_v["flat_W0"].shape[0])
    jaxpr = str(jax.make_jaxpr(
        lambda *a: _fd_wing_vmapped(*a, n_pairs=n_pairs_v))(*args_v))
    n_while = jaxpr.count("while[")
    assert n_while == 1, f"vmapped FD must be ONE while_loop, got {n_while}"
    assert not any(c in jaxpr for c in ("psum", "all_gather", "ppermute")), \
        "vmapped FD must be collective-free"
    print("[peel-dryrun] vmapped csr FD: whole Phase 2 is ONE while_loop, "
          "zero collectives ✓")

    # --- TIP csr at 512 devices: the entity-agnostic core's second
    # instantiation gets the same structural guarantees as wing
    from repro.core.peel import tip_decomposition

    bf0 = wed.pair_butterflies0()
    n = g.n_u
    tal = D.shard_tip_pairs(wed, bf0, 512, aligned=True)
    tfn = D.make_cd_round_tip_csr(mesh, "peel", n)
    tpe = jnp.zeros((n + 1,), bool)
    tsup = jnp.zeros((n + 1,), jnp.int32)
    ttxt = tfn.lower(tpe, tsup, jnp.asarray(tal["dst"]),
                     jnp.asarray(tal["src"]),
                     jnp.asarray(tal["bf"])).compile().as_text()
    n_tip = ttxt.count("all-reduce(") + ttxt.count("all-reduce-start(")
    assert n_tip == 1, f"aligned tip CD must pay ONE psum, found {n_tip}"
    print("[peel-dryrun] vertex-aligned tip csr CD compiled at 512 "
          "devices; exactly ONE all-reduce per round ✓")

    res_t = tip_decomposition(g, side="u", P=64, engine="csr")
    packed_t = D.pack_fd_partitions_tip_csr(
        wed, bf0, res_t.part, res_t.support_init,
        res_t.stats.p_effective, stacked=True)
    n_parts_t = packed_t["st_pa"].shape[0]
    pad_t = (-n_parts_t) % 512

    def padt(x):
        if pad_t == 0:
            return jnp.asarray(x)
        fill = np.zeros((pad_t,) + x.shape[1:], dtype=x.dtype)
        return jnp.asarray(np.concatenate([x, fill], 0))

    args_t = tuple(padt(packed_t[k]) for k in
                   ("st_pa", "st_pb", "st_bf", "mine", "sup0"))
    fd_t = shard_map(jax.vmap(D._fd_body_one_partition_tip_csr), mesh=mesh,
                     in_specs=tuple(P("peel") for _ in args_t),
                     out_specs=(P("peel"), P("peel")))
    fd_t_txt = jax.jit(fd_t).lower(*args_t).compile().as_text()
    bad_t = [w for w in ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute")
             if w in fd_t_txt]
    assert not bad_t, f"tip csr FD must be collective-free, found {bad_t}"
    print("[peel-dryrun] tip csr FD peel compiled at 512 devices; "
          "NO collectives in HLO ✓")

    packed_tv = D.pack_fd_partitions_tip_csr(
        wed, bf0, res_t.part, res_t.support_init,
        res_t.stats.p_effective, bucket=True)
    tjaxpr = str(jax.make_jaxpr(_fd_tip_vmapped)(
        jnp.asarray(packed_tv["pa"]), jnp.asarray(packed_tv["pb"]),
        jnp.asarray(packed_tv["bf"]), jnp.asarray(packed_tv["mine"]),
        jnp.asarray(packed_tv["sup0"])))
    n_tw = tjaxpr.count("while[")
    assert n_tw == 1, f"vmapped tip FD must be ONE while_loop, got {n_tw}"
    assert not any(c in tjaxpr for c in ("psum", "all_gather", "ppermute")), \
        "vmapped tip FD must be collective-free"
    print("[peel-dryrun] vmapped tip FD: whole Phase 2 is ONE while_loop, "
          "zero collectives ✓")

    # --- hierarchical CD at 512 devices: the ONE logical psum staged
    # over a (16, 32) 2-D mesh — exactly two all-reduces with nested
    # replica groups, bit-identical int32 reduction
    from repro.launch.mesh import make_peel_mesh_2d

    mesh2 = make_peel_mesh_2d(512)
    hfn = D.make_cd_round_csr_pair_aligned(
        mesh2, ("grp", "loc"), pal["Pmax"], g.m)
    htxt = hfn.lower(peeled, jnp.asarray(pal["alive"]),
                     jnp.asarray(pal["W0"]), sup,
                     jnp.asarray(pal["we1"]), jnp.asarray(pal["we2"]),
                     jnp.asarray(pal["wp"])).compile().as_text()
    n_h = htxt.count("all-reduce(") + htxt.count("all-reduce-start(")
    assert n_h == 2, f"staged CD psum must be TWO all-reduces, found {n_h}"
    hflat = htxt.replace(" ", "")
    assert "{0,1,2,3" in hflat and "{0,32,64," in hflat, \
        "staged CD psum must carry nested replica groups"
    print("[peel-dryrun] hierarchical pair-aligned CD compiled at 512 "
          "devices (16 groups x 32); one logical psum = two staged "
          "all-reduces with nested replica groups ✓")
    return 0


def _emit_hierarchy(args, g, result, kind: str, stats=None) -> None:
    """Build the dense-subgraph hierarchy from peel output and write the
    versioned artifact (see ``repro.hierarchy``): decompose once, serve
    forever.  ``result`` is a PeelResult whenever one exists — the
    single-device engines AND the distributed paths
    (``return_result=True``) — so the artifact always carries the
    PeelStats + CD partition provenance; ``stats`` is only the fallback
    row for raw-θ input."""
    import time

    import numpy as np

    from repro.core.peel import PeelResult
    from repro.hierarchy import (build_hierarchy, density_profile,
                                 save_hierarchy, top_densest_leaves)

    meta = None
    if not isinstance(result, PeelResult) and stats:
        meta = dict(stats=stats)
    t0 = time.perf_counter()
    h = build_hierarchy(g, result, kind=kind, side=args.side, meta=meta)
    dt = time.perf_counter() - t0
    save_hierarchy(args.emit_hierarchy, h)
    lv = h.levels
    print(f"[peel] hierarchy: {h.n_nodes} nodes over {lv.size} levels "
          f"built in {dt * 1e3:.1f} ms -> {args.emit_hierarchy}")
    if lv.size:
        prof = density_profile(h, int(lv[0]))
        top = top_densest_leaves(h, 3)
        print(f"[peel] k={int(lv[0])}: {prof['n_components']} components; "
              f"densest leaves: "
              f"{np.round(top['density'], 3).tolist()} "
              f"at k={top['level'].tolist()}")


def run(args) -> dict:
    """Peel the graph ``args`` names; returns ``dict(graph, theta,
    result, stats)`` (``result`` is the PeelResult).  ``main`` is the
    command-line wrapper; ``chip_smoke.py`` calls this in-process."""
    import jax
    import numpy as np

    from repro.core import distributed as D
    from repro.core.graph import paper_proxy_dataset, powerlaw_bipartite
    from repro.core.peel import tip_decomposition, wing_decomposition
    from repro.launch.mesh import make_peel_mesh

    n_dev = len(jax.devices())
    _validate(args, n_dev)

    sup0 = None
    if args.edges:
        # real-data path: out-of-core ingest → bounded-tile ⋈init →
        # the same CD/FD engines, fed through sup0 injection (the
        # engines never see the O(Σ deg²) wedge list at once)
        from types import SimpleNamespace

        from repro.core import csr as csrmod
        from repro.data import ingest_edges

        ig = ingest_edges(args.edges, out_dir=args.ingest_dir)
        g = ig.as_graph()
        print(f"[peel] ingested {args.edges}: |U|={ig.n_u} "
              f"|V|={ig.n_v} |E|={ig.m}")
        if args.kind == "tip" and args.side == "v":
            # wedge centers must sit on the peeled side's opposite
            # partition: transpose the CSR view, not the data
            src = SimpleNamespace(n_u=ig.n_v, n_v=ig.n_u, m=ig.m,
                                  csr_v=ig.csr_u)
        else:
            src = ig
        sup_e, sup_u, total_bf, tstats = csrmod.tiled_butterfly_init(
            src, tile_wedges=args.tile_wedges,
            use_pallas=args.use_pallas)
        sup0 = sup_e if args.kind == "wing" else sup_u
        print(f"[peel] tiled init: butterflies={total_bf} "
              f"tiles={tstats.n_tiles} wedges={tstats.n_wedges} "
              f"peak_tile_wedges={tstats.peak_tile_wedges}")
    elif args.dataset:
        g = paper_proxy_dataset(args.dataset)
    else:
        g = powerlaw_bipartite(args.n_u, args.n_v, args.m, alpha=args.alpha,
                               seed=args.seed)
    print(f"[peel] graph |U|={g.n_u} |V|={g.n_v} |E|={g.m}")

    stats_out = {}
    result = None  # PeelResult when available (single-device OR dist.)
    if args.kind == "wing":
        if n_dev > 1:
            mesh = make_peel_mesh()
            theta, stats_out, result = D.distributed_wing_decomposition(
                g, mesh, P_parts=args.parts, engine=args.engine,
                aligned=args.aligned, return_result=True)
            print(f"[peel] distributed over {stats_out['n_dev']} devices: "
                  f"{stats_out}")
        else:
            res = wing_decomposition(
                g, P=args.parts, engine=args.engine,
                fd_driver=args.fd_driver, use_pallas=args.use_pallas,
                sup0=sup0)
            result = res
            theta = res.theta
            s = res.stats
            stats_out = s.as_dict()
            print(f"[peel] engine={s.engine} rho_cd={s.rho_cd} "
                  f"rho_fd_max={s.rho_fd_max} updates={s.updates} "
                  f"sync_reduction={s.sync_reduction:.1f}x")
    else:
        if n_dev > 1:
            mesh = make_peel_mesh()
            theta, stats_out, result = D.distributed_tip_decomposition(
                g, mesh, side=args.side, P_parts=args.parts,
                engine=args.engine, aligned=args.aligned,
                fd_driver=args.fd_driver, return_result=True)
            print(f"[peel] distributed over {stats_out['n_dev']} devices: "
                  f"{stats_out}")
        else:
            res = tip_decomposition(
                g, side=args.side, P=args.parts, engine=args.engine,
                fd_driver=args.fd_driver, use_pallas=args.use_pallas,
                sup0=sup0)
            result = res
            theta = res.theta
            s = res.stats
            stats_out = s.as_dict()
            print(f"[peel] engine={s.engine} side={s.side} "
                  f"rho_cd={s.rho_cd} rho_fd_max={s.rho_fd_max} "
                  f"recounts={s.recounts}")

    if (result is not None
            and getattr(result, "timeline", None) is not None):
        stats_out["timeline"] = result.timeline.summary()
        print(f"[peel] timeline: {stats_out['timeline']}")
    import hashlib
    theta_sha = hashlib.sha256(
        np.asarray(theta, dtype=np.int64).tobytes()).hexdigest()
    stats_out["theta_sha256"] = theta_sha
    print(f"[peel] theta: max={int(theta.max()) if theta.size else 0} "
          f"levels={len(set(theta.tolist()))} sha256={theta_sha}")
    if args.emit_hierarchy:
        _emit_hierarchy(args, g, result if result is not None else theta,
                        kind=args.kind, stats=stats_out)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(dict(theta=theta.tolist(), stats=stats_out), f)
    return dict(graph=g, theta=theta, result=result, stats=stats_out)


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (shared with ``chip_smoke.py``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", "--mode", dest="kind",
                    choices=["wing", "tip"], default="wing",
                    help="entity universe to peel: edges (wing) or "
                         "vertices (tip); flags below apply uniformly")
    ap.add_argument("--dataset", default=None)
    ap.add_argument("--edges", default=None, metavar="PATH",
                    help="peel a real graph: KONECT/SNAP-style edge "
                         "list (TSV/space separated, %% or # comments, "
                         "1- or 0-based ids, negative third column = "
                         "deletion).  Ingested out of core (chunked "
                         "dedup + degree-ordered relabel to a "
                         "memory-mapped CSR), then counted in bounded "
                         "wedge tiles (--tile-wedges) before the "
                         "engines run.  Exclusive with --dataset")
    ap.add_argument("--tile-wedges", type=int, default=1 << 20,
                    help="wedge-tile budget for the --edges counting "
                         "pass: peak host memory is O(tile) and peak "
                         "device memory one kernel block, never the "
                         "full O(Σ deg²) wedge list (default 2^20)")
    ap.add_argument("--ingest-dir", default=None, metavar="DIR",
                    help="cache directory for the --edges ingestion "
                         "artifacts (default: <edges>.ingest next to "
                         "the input; re-runs hit the cache)")
    ap.add_argument("--n-u", type=int, default=400)
    ap.add_argument("--n-v", type=int, default=200)
    ap.add_argument("--m", type=int, default=2000)
    ap.add_argument("--alpha", type=float, default=1.3,
                    help="degree-skew exponent of the generated power-law "
                         "graph (smaller = flatter degrees, more wedges)")
    ap.add_argument("--parts", type=int, default=16)
    ap.add_argument("--engine", default=None,
                    choices=["beindex", "dense", "csr"],
                    help="beindex (wing only), dense, or csr (the "
                         "scalable path for both kinds); default: "
                         "beindex for wing, csr for tip")
    ap.add_argument("--fd-driver", default="device",
                    choices=["device", "vmapped", "host"],
                    help="csr FD cascade driver: one while_loop per "
                         "partition (device), ONE while_loop for the "
                         "whole Phase 2 (vmapped — single dispatch), or "
                         "per-round dispatch (host; single-device A/B "
                         "baseline only)")
    ap.add_argument("--aligned", "--pair-aligned", dest="aligned",
                    action="store_true",
                    help="distributed one-psum CD sharding: keep every "
                         "segment's items on one device (wing csr: "
                         "pair-aligned wedges; tip csr: vertex-aligned "
                         "pair entries; wing beindex: bloom-aligned "
                         "links)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="csr engines only: run CD support updates "
                         "through the blocked Pallas kernels (and, for "
                         "wing --fd-driver vmapped, inside the FD "
                         "while_loop)")
    ap.add_argument("--side", default="u")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--emit-hierarchy", default=None, metavar="PATH",
                    help="build the dense-subgraph hierarchy from the "
                         "decomposition and save it as a versioned npz "
                         "artifact (load with "
                         "repro.hierarchy.load_hierarchy)")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable the observability layer and write a "
                         "Chrome-trace JSON of the run (open in "
                         "Perfetto / chrome://tracing): peel/cd/fd "
                         "spans, per-round cd.round/fd.round events, "
                         "hierarchy build spans.  Off by default — the "
                         "traced programs are byte-identical without it")
    return ap


def main():
    args = build_parser().parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        from repro import obs
        obs.enable()
    if args.dryrun:
        rc = _dryrun()
    else:
        run(args)
        rc = 0
    if args.trace:
        tracer = obs.get_tracer()
        tracer.save(args.trace)
        print(f"[peel] trace: {len(tracer.events)} events -> "
              f"{args.trace}")
    sys.exit(rc)


if __name__ == "__main__":
    main()
