"""Multi-device PBNG (shard_map) — run in a subprocess with forced host
device count so the main test process keeps a single device."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(src: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_distributed_wing_matches_oracle():
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite
        from repro.core import ref
        from repro.core.distributed import distributed_wing_decomposition
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        for seed in (0, 1, 2):
            g = random_bipartite(16, 12, 48, seed=seed)
            want = ref.bup_wing_ref(g)
            theta, stats = distributed_wing_decomposition(
                g, mesh, axis="peel", P_parts=4)
            assert np.array_equal(theta, want), seed
        print("OK")
    """)
    assert "OK" in out


def test_distributed_matches_single_device_engine():
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.graph import powerlaw_bipartite
        from repro.core.distributed import distributed_wing_decomposition
        from repro.core.peel import wing_decomposition
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        g = powerlaw_bipartite(100, 50, 420, seed=5)
        theta, stats = distributed_wing_decomposition(
            g, mesh, axis="peel", P_parts=6)
        ref_theta = wing_decomposition(g, P=6, engine="beindex").theta
        assert np.array_equal(theta, ref_theta)
        assert stats["rho_cd"] > 0 and stats["rho_fd_max"] > 0
        print("OK", stats)
    """)
    assert "OK" in out


def test_fd_hlo_has_no_collectives():
    """The paper's 'no global synchronization' claim, checked structurally:
    the FD phase HLO must contain no collective ops."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.graph import random_bipartite
        from repro.core.beindex import build_beindex
        from repro.core.peel import wing_decomposition
        from repro.core import distributed as D
        g = random_bipartite(20, 16, 64, seed=3)
        be = build_beindex(g)
        res = wing_decomposition(g, P=4, engine="beindex", be=be)
        packed = D.pack_fd_partitions(
            g, be, res.part, res.support_init, res.stats.p_effective)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        n_parts = packed["le"].shape[0]
        pad = (-n_parts) % 8
        def padp(x):
            if pad == 0: return jnp.asarray(x)
            fill = np.zeros((pad,)+x.shape[1:], dtype=x.dtype)
            return jnp.asarray(np.concatenate([x, fill], 0))
        args = tuple(padp(packed[k]) for k in
                     ("le","lt","lb","alive0","canon","k0","sup0","mine"))
        from jax import shard_map
        vb = jax.vmap(D._fd_body_one_partition)
        fn = shard_map(vb, mesh=mesh,
                       in_specs=tuple(P("peel") for _ in args),
                       out_specs=(P("peel"), P("peel")))
        txt = jax.jit(fn).lower(*args).compile().as_text()
        bad = [w for w in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute")
               if w in txt]
        assert not bad, bad
        print("OK no collectives in FD")
    """)
    assert "OK" in out


def test_cd_round_single_psum_pair():
    """CD rounds synchronize via psum only (one c + one loss reduction)."""
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite
        from repro.core.beindex import build_beindex
        from repro.core import distributed as D
        import jax.numpy as jnp
        g = random_bipartite(20, 16, 64, seed=3)
        be = build_beindex(g)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        st = D.shard_links(be, g.m, 8)
        fn = D.make_cd_round(mesh, "peel", st.nb, g.m)
        peeled = jnp.zeros((g.m + 1,), bool)
        sup = jnp.concatenate([st.support, jnp.zeros((1,), jnp.int32)])
        txt = fn.lower(peeled, st.alive_link, st.k_alive, sup,
                       st.le, st.lt, st.lb).compile().as_text()
        n_ar = txt.count("all-reduce-start") or txt.count("all-reduce(")
        assert n_ar <= 3, f"too many collectives per CD round: {n_ar}"
        print("OK", n_ar)
    """)
    assert "OK" in out


def test_distributed_wing_csr_matches_oracle():
    """csr engine on a mesh: wedge-sharded CD + wedge-packed FD."""
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite, powerlaw_bipartite
        from repro.core import ref
        from repro.core.distributed import distributed_wing_decomposition
        from repro.core.peel import wing_decomposition
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        for seed in (0, 1, 2):
            g = random_bipartite(16, 12, 48, seed=seed)
            want = ref.bup_wing_ref(g)
            theta, stats = distributed_wing_decomposition(
                g, mesh, axis="peel", P_parts=4, engine="csr")
            assert np.array_equal(theta, want), seed
            assert stats["engine"] == "csr"
        g = powerlaw_bipartite(100, 50, 420, seed=5)
        theta, stats = distributed_wing_decomposition(
            g, mesh, axis="peel", P_parts=6, engine="csr")
        ref_theta = wing_decomposition(g, P=6, engine="csr").theta
        assert np.array_equal(theta, ref_theta)
        print("OK", stats)
    """)
    assert "OK" in out


def test_csr_fd_hlo_has_no_collectives():
    """csr FD partitions peel under shard_map with zero collectives —
    the paper's Phase-2 claim for the engine that scales."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.graph import random_bipartite
        from repro.core import csr
        from repro.core.peel import wing_decomposition
        from repro.core import distributed as D
        from jax import shard_map
        g = random_bipartite(20, 16, 64, seed=3)
        wed = csr.build_wedges(g)
        res = wing_decomposition(g, P=4, engine="csr")
        packed = D.pack_fd_partitions_csr(
            wed, res.part, res.support_init, res.stats.p_effective)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        n_parts = packed["we1"].shape[0]
        pad = (-n_parts) % 8
        def padp(x):
            if pad == 0: return jnp.asarray(x)
            fill = np.zeros((pad,)+x.shape[1:], dtype=x.dtype)
            return jnp.asarray(np.concatenate([x, fill], 0))
        args = tuple(padp(packed[k]) for k in
                     ("we1","we2","wp","alive0","W0","sup0","mine"))
        fn = shard_map(jax.vmap(D._fd_body_one_partition_csr), mesh=mesh,
                       in_specs=tuple(P("peel") for _ in args),
                       out_specs=(P("peel"), P("peel")))
        txt = jax.jit(fn).lower(*args).compile().as_text()
        bad = [w for w in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute")
               if w in txt]
        assert not bad, bad
        print("OK no collectives in csr FD")
    """)
    assert "OK" in out


def test_csr_cd_round_two_psums():
    """csr CD rounds synchronize via psum only (one c + one loss)."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite
        from repro.core import csr
        from repro.core import distributed as D
        g = random_bipartite(20, 16, 64, seed=3)
        wed = csr.build_wedges(g)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        st = D.shard_wedges(wed, 8)
        fn = D.make_cd_round_csr(mesh, "peel", st.n_pairs, g.m)
        peeled = jnp.zeros((g.m + 1,), bool)
        sup = jnp.concatenate([st.support, jnp.zeros((1,), jnp.int32)])
        txt = fn.lower(peeled, st.alive_w, st.W_pad, sup,
                       st.we1, st.we2, st.wp).compile().as_text()
        n_ar = txt.count("all-reduce-start") or txt.count("all-reduce(")
        assert n_ar <= 3, f"too many collectives per csr CD round: {n_ar}"
        print("OK", n_ar)
    """)
    assert "OK" in out


def test_pair_aligned_single_psum():
    """Pair-aligned csr CD round must contain exactly one all-reduce —
    c_p and W_p are shard-local once every pair's wedges live on one
    device — and θ must stay bit-identical to the oracle."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite, powerlaw_bipartite
        from repro.core import csr, ref
        from repro.core import distributed as D
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        g = powerlaw_bipartite(80, 40, 350, seed=2)
        wed = csr.build_wedges(g)
        packed = D.shard_wedges_pair_aligned(wed, 8)
        fn = D.make_cd_round_csr_pair_aligned(
            mesh, "peel", packed["Pmax"], g.m)
        peeled = jnp.zeros((g.m + 1,), bool)
        sup = jnp.zeros((g.m + 1,), jnp.int32)
        txt = fn.lower(peeled, jnp.asarray(packed["alive"]),
                       jnp.asarray(packed["W0"]), sup,
                       jnp.asarray(packed["we1"]), jnp.asarray(packed["we2"]),
                       jnp.asarray(packed["wp"])).compile().as_text()
        n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
        assert n == 1, n
        for seed in (0, 1, 2):
            g = random_bipartite(16, 12, 48, seed=seed)
            want = ref.bup_wing_ref(g)
            theta, stats = D.distributed_wing_decomposition(
                g, mesh, axis="peel", P_parts=4, engine="csr",
                pair_aligned=True)
            assert np.array_equal(theta, want), seed
            assert stats["cd_sharding"] == "pair_aligned"
        print("OK", n)
    """)
    assert "OK" in out


def test_pair_aligned_single_device_matches_engine():
    """Degenerate 1-device mesh: pair-aligned CD must still agree with
    the single-device csr engine (same algebra, no collectives to
    save)."""
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.graph import powerlaw_bipartite
        from repro.core.distributed import distributed_wing_decomposition
        from repro.core.peel import wing_decomposition
        mesh = Mesh(np.array(jax.devices()).reshape(1), ("peel",))
        g = powerlaw_bipartite(100, 50, 420, seed=5)
        theta, stats = distributed_wing_decomposition(
            g, mesh, axis="peel", P_parts=6, engine="csr",
            pair_aligned=True)
        ref_theta = wing_decomposition(g, P=6, engine="csr").theta
        assert np.array_equal(theta, ref_theta)
        assert stats["n_dev"] == 1
        print("OK", stats)
    """, n_dev=1)
    assert "OK" in out


def test_pair_aligned_cd_512dev_single_psum():
    """The production-mesh shape: ONE all-reduce per pair-aligned CD
    round at 512 dry-run devices (the same lowering `launch.peel
    --dryrun` asserts, kept in the suite so regressions fail fast)."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import powerlaw_bipartite
        from repro.core import csr
        from repro.core import distributed as D
        mesh = Mesh(np.array(jax.devices()).reshape(512), ("peel",))
        g = powerlaw_bipartite(100, 50, 500, seed=1)
        wed = csr.build_wedges(g)
        packed = D.shard_wedges_pair_aligned(wed, 512)
        fn = D.make_cd_round_csr_pair_aligned(
            mesh, "peel", packed["Pmax"], g.m)
        peeled = jnp.zeros((g.m + 1,), bool)
        sup = jnp.zeros((g.m + 1,), jnp.int32)
        txt = fn.lower(peeled, jnp.asarray(packed["alive"]),
                       jnp.asarray(packed["W0"]), sup,
                       jnp.asarray(packed["we1"]), jnp.asarray(packed["we2"]),
                       jnp.asarray(packed["wp"])).compile().as_text()
        n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
        assert n == 1, n
        print("OK", n)
    """, n_dev=512)
    assert "OK" in out


def test_distributed_tip_matches_oracle():
    """Every distributed tip path — csr (default), csr aligned, csr
    vmapped-FD, and the explicit dense fallback — must be θ-bit-identical
    to the BUP oracle and to each other."""
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite
        from repro.core import ref
        from repro.core.distributed import distributed_tip_decomposition
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        for seed in (0, 1):
            g = random_bipartite(16, 12, 48, seed=seed)
            for side in ("u", "v"):
                want = ref.bup_tip_ref(g, side)
                theta, stats = distributed_tip_decomposition(
                    g, mesh, side=side, P_parts=4)
                assert np.array_equal(theta, want), (seed, side)
                assert stats["engine"] == "csr"
                assert stats["side"] == side
                for kw in (dict(engine="dense"),
                           dict(engine="csr", aligned=True),
                           dict(engine="csr", aligned=True,
                                fd_driver="vmapped")):
                    t2, s2 = distributed_tip_decomposition(
                        g, mesh, side=side, P_parts=4, **kw)
                    assert np.array_equal(t2, want), (seed, side, kw)
        print("OK")
    """)
    assert "OK" in out


def test_distributed_tip_csr_matches_single_device_and_dense():
    """csr tip on a mesh == single-device csr engine == the dense
    distributed fallback, θ bit-for-bit; provenance rides along when
    asked for."""
    out = _run("""
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro.core.graph import powerlaw_bipartite
        from repro.core.distributed import distributed_tip_decomposition
        from repro.core.peel import tip_decomposition
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        g = powerlaw_bipartite(100, 50, 420, seed=5)
        theta, stats, res = distributed_tip_decomposition(
            g, mesh, side="u", P_parts=6, engine="csr", aligned=True,
            return_result=True)
        ref_theta = tip_decomposition(g, side="u", P=6, engine="csr").theta
        assert np.array_equal(theta, ref_theta)
        td, _ = distributed_tip_decomposition(
            g, mesh, side="u", P_parts=6, engine="dense")
        assert np.array_equal(td, theta)
        assert stats["cd_sharding"] == "vertex_aligned"
        assert stats["rho_cd"] > 0 and stats["rho_fd_max"] > 0
        prov = res.provenance()
        assert prov["stats"]["engine"] == "csr"
        assert prov["stats"]["side"] == "u"
        assert prov["part"].shape == theta.shape
        assert prov["ranges"].size == stats["p_effective"] + 1
        print("OK", stats)
    """)
    assert "OK" in out


def test_tip_csr_cd_single_psum():
    """Tip csr CD rounds pay exactly ONE psum — pair butterflies are
    static, so there is no dying-count collective at all; aligned and
    round-robin layouts share the guarantee, and aligned θ is
    oracle-exact."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite, powerlaw_bipartite
        from repro.core import csr, ref
        from repro.core import distributed as D
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        g = powerlaw_bipartite(80, 40, 350, seed=2)
        wed = csr.build_wedges(g)
        bf0 = wed.pair_butterflies0()
        fn = D.make_cd_round_tip_csr(mesh, "peel", g.n_u)
        peeled = jnp.zeros((g.n_u + 1,), bool)
        sup = jnp.zeros((g.n_u + 1,), jnp.int32)
        for aligned in (False, True):
            bl = D.shard_tip_pairs(wed, bf0, 8, aligned=aligned)
            txt = fn.lower(peeled, sup, jnp.asarray(bl["dst"]),
                           jnp.asarray(bl["src"]),
                           jnp.asarray(bl["bf"])).compile().as_text()
            n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
            assert n == 1, (aligned, n)
        for seed in (0, 1, 2):
            g = random_bipartite(16, 12, 48, seed=seed)
            want = ref.bup_tip_ref(g, "u")
            theta, stats = D.distributed_tip_decomposition(
                g, mesh, side="u", P_parts=4, engine="csr", aligned=True)
            assert np.array_equal(theta, want), seed
        print("OK")
    """)
    assert "OK" in out


def test_tip_csr_single_device_matches_engine():
    """Degenerate 1-device mesh: distributed tip csr must still agree
    with the single-device csr engine, and the aligned CD round still
    lowers to its single psum."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import powerlaw_bipartite
        from repro.core import csr
        from repro.core import distributed as D
        from repro.core.peel import tip_decomposition
        mesh = Mesh(np.array(jax.devices()).reshape(1), ("peel",))
        g = powerlaw_bipartite(100, 50, 420, seed=5)
        theta, stats = D.distributed_tip_decomposition(
            g, mesh, side="u", P_parts=6, engine="csr", aligned=True)
        ref_theta = tip_decomposition(g, side="u", P=6, engine="csr").theta
        assert np.array_equal(theta, ref_theta)
        assert stats["n_dev"] == 1
        wed = csr.build_wedges(g)
        bl = D.shard_tip_pairs(wed, wed.pair_butterflies0(), 1,
                               aligned=True)
        fn = D.make_cd_round_tip_csr(mesh, "peel", g.n_u)
        txt = fn.lower(jnp.zeros((g.n_u + 1,), bool),
                       jnp.zeros((g.n_u + 1,), jnp.int32),
                       jnp.asarray(bl["dst"]), jnp.asarray(bl["src"]),
                       jnp.asarray(bl["bf"])).compile().as_text()
        print("OK", stats["rho_cd"])
    """, n_dev=1)
    assert "OK" in out


def test_tip_csr_cd_512dev_single_psum_and_vmapped_fd():
    """Production-mesh shape for tip: ONE all-reduce per aligned CD
    round at 512 dry-run devices, plus the single-`while` collective-free
    vmapped FD jaxpr (the same lowerings `launch.peel --dryrun`
    asserts)."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import powerlaw_bipartite
        from repro.core import csr
        from repro.core import distributed as D
        from repro.core.peel import tip_decomposition, _fd_tip_vmapped
        mesh = Mesh(np.array(jax.devices()).reshape(512), ("peel",))
        g = powerlaw_bipartite(100, 50, 500, seed=1)
        wed = csr.build_wedges(g)
        bf0 = wed.pair_butterflies0()
        bl = D.shard_tip_pairs(wed, bf0, 512, aligned=True)
        fn = D.make_cd_round_tip_csr(mesh, "peel", g.n_u)
        txt = fn.lower(jnp.zeros((g.n_u + 1,), bool),
                       jnp.zeros((g.n_u + 1,), jnp.int32),
                       jnp.asarray(bl["dst"]), jnp.asarray(bl["src"]),
                       jnp.asarray(bl["bf"])).compile().as_text()
        n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
        assert n == 1, n
        res = tip_decomposition(g, side="u", P=8, engine="csr")
        packed = D.pack_fd_partitions_tip_csr(
            wed, bf0, res.part, res.support_init,
            res.stats.p_effective, bucket=True)
        jaxpr = str(jax.make_jaxpr(_fd_tip_vmapped)(
            jnp.asarray(packed["pa"]), jnp.asarray(packed["pb"]),
            jnp.asarray(packed["bf"]), jnp.asarray(packed["mine"]),
            jnp.asarray(packed["sup0"])))
        nw = jaxpr.count("while[")
        assert nw == 1, nw
        assert not any(c in jaxpr for c in
                       ("psum", "all_gather", "ppermute"))
        print("OK", n, nw)
    """, n_dev=512)
    assert "OK" in out


def test_tip_csr_fd_hlo_has_no_collectives():
    """Tip csr FD partitions peel under shard_map with zero collectives
    — the Phase-2 claim for the entity-agnostic core's second
    instantiation."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.graph import random_bipartite
        from repro.core import csr
        from repro.core.peel import tip_decomposition
        from repro.core import distributed as D
        from jax import shard_map
        g = random_bipartite(20, 16, 64, seed=3)
        wed = csr.build_wedges(g)
        bf0 = wed.pair_butterflies0()
        res = tip_decomposition(g, side="u", P=4, engine="csr")
        packed = D.pack_fd_partitions_tip_csr(
            wed, bf0, res.part, res.support_init,
            res.stats.p_effective, stacked=True)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        n_parts = packed["st_pa"].shape[0]
        pad = (-n_parts) % 8
        def padp(x):
            if pad == 0: return jnp.asarray(x)
            fill = np.zeros((pad,)+x.shape[1:], dtype=x.dtype)
            return jnp.asarray(np.concatenate([x, fill], 0))
        args = tuple(padp(packed[k]) for k in
                     ("st_pa","st_pb","st_bf","mine","sup0"))
        fn = shard_map(jax.vmap(D._fd_body_one_partition_tip_csr),
                       mesh=mesh,
                       in_specs=tuple(P("peel") for _ in args),
                       out_specs=(P("peel"), P("peel")))
        txt = jax.jit(fn).lower(*args).compile().as_text()
        bad = [w for w in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute")
               if w in txt]
        assert not bad, bad
        print("OK no collectives in tip csr FD")
    """)
    assert "OK" in out


def test_emit_hierarchy_distributed_tip_wing_parity(tmp_path):
    """--emit-hierarchy on the distributed tip csr path must attach the
    SAME provenance the wing path attaches: engine/side-tagged PeelStats
    plus the CD partition/ranges/⋈init arrays (satellite of the
    entity-agnostic core refactor)."""
    wing_art = tmp_path / "wing.npz"
    tip_art = tmp_path / "tip.npz"
    out = _run(f"""
        import numpy as np, jax
        from repro.core.graph import powerlaw_bipartite
        from repro.core.distributed import (
            distributed_tip_decomposition, distributed_wing_decomposition)
        from repro.hierarchy import build_hierarchy, save_hierarchy
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        g = powerlaw_bipartite(60, 40, 260, seed=7)
        _, _, res_w = distributed_wing_decomposition(
            g, mesh, P_parts=4, engine="csr", pair_aligned=True,
            return_result=True)
        _, _, res_t = distributed_tip_decomposition(
            g, mesh, side="u", P_parts=4, engine="csr", aligned=True,
            return_result=True)
        save_hierarchy({str(wing_art)!r},
                       build_hierarchy(g, res_w, kind="wing"))
        save_hierarchy({str(tip_art)!r},
                       build_hierarchy(g, res_t, kind="tip", side="u"))
        print("OK")
    """)
    assert "OK" in out
    from repro.hierarchy import load_hierarchy

    hw = load_hierarchy(str(wing_art))
    ht = load_hierarchy(str(tip_art))
    for h, side in ((hw, ""), (ht, "u")):
        assert h.meta["stats"]["engine"] == "csr"
        assert h.meta["stats"]["side"] == side
        for key in ("part", "ranges", "support_init"):
            assert key in h.meta, (side, key)
            assert np.asarray(h.meta[key]).size > 0
    # parity: identical provenance key sets on both paths
    assert set(hw.meta) == set(ht.meta)


def test_bloom_aligned_single_psum():
    """Bloom-aligned CD round must contain exactly one all-reduce."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import powerlaw_bipartite
        from repro.core.beindex import build_beindex
        from repro.core import distributed as D
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        g = powerlaw_bipartite(80, 40, 350, seed=2)
        be = build_beindex(g)
        packed = D.shard_links_bloom_aligned(be, g.m, 8)
        fn = D.make_cd_round_bloom(mesh, "peel", packed["Bmax"], g.m)
        peeled = jnp.zeros((g.m + 1,), bool)
        sup = jnp.zeros((g.m + 1,), jnp.int32)
        txt = fn.lower(peeled, jnp.asarray(packed["alive"]),
                       jnp.asarray(packed["k0"]), sup,
                       jnp.asarray(packed["le"]), jnp.asarray(packed["lt"]),
                       jnp.asarray(packed["lb"])).compile().as_text()
        n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
        assert n == 1, n
        print("OK", n)
    """)
    assert "OK" in out

def test_hierarchical_cd_8dev_staged_psum_replica_groups():
    """Hierarchical CD on a 2-D ("grp", "loc") mesh: the round's single
    logical psum lowers to exactly TWO staged all-reduces with nested
    replica groups — reduce within each group of co-located devices
    first ({{0,1,2,3},{4,5,6,7}} for the 2x4 mesh), then across groups
    ({{0,4},{1,5},{2,6},{3,7}}) — and θ stays bit-identical to both the
    flat 1-D mesh and the BUP oracle (int32 sums are exact under any
    grouping)."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.core.graph import random_bipartite, powerlaw_bipartite
        from repro.core import csr, ref
        from repro.core import distributed as D
        from repro.launch.mesh import make_peel_mesh_2d
        mesh2 = make_peel_mesh_2d(8)
        assert mesh2.devices.shape == (2, 4), mesh2.devices.shape
        g = powerlaw_bipartite(80, 40, 350, seed=2)
        wed = csr.build_wedges(g)
        packed = D.shard_wedges_pair_aligned(wed, 8)
        fn = D.make_cd_round_csr_pair_aligned(
            mesh2, ("grp", "loc"), packed["Pmax"], g.m)
        peeled = jnp.zeros((g.m + 1,), bool)
        sup = jnp.zeros((g.m + 1,), jnp.int32)
        txt = fn.lower(peeled, jnp.asarray(packed["alive"]),
                       jnp.asarray(packed["W0"]), sup,
                       jnp.asarray(packed["we1"]), jnp.asarray(packed["we2"]),
                       jnp.asarray(packed["wp"])).compile().as_text()
        n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
        assert n == 2, n
        flat = txt.replace(" ", "")
        assert "{{0,1,2,3},{4,5,6,7}}" in flat, "missing intra-group stage"
        assert "{{0,4},{1,5},{2,6},{3,7}}" in flat, "missing cross-group stage"
        mesh1 = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
        for seed in (0, 1, 2):
            g = random_bipartite(16, 12, 48, seed=seed)
            want = ref.bup_wing_ref(g)
            th, _ = D.distributed_wing_decomposition(
                g, mesh2, axis=("grp", "loc"), P_parts=4, engine="csr",
                pair_aligned=True)
            tf, _ = D.distributed_wing_decomposition(
                g, mesh1, axis="peel", P_parts=4, engine="csr",
                pair_aligned=True)
            assert np.array_equal(th, want), seed
            assert np.array_equal(th, tf), seed
        print("OK", n)
    """)
    assert "OK" in out


def test_hierarchical_tip_cd_8dev():
    """The same two-stage lowering for the tip CD round, and θ parity
    for the full hierarchical distributed tip decomposition."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from repro.core.graph import random_bipartite, powerlaw_bipartite
        from repro.core import csr, ref
        from repro.core import distributed as D
        from repro.launch.mesh import make_peel_mesh_2d
        mesh2 = make_peel_mesh_2d(8)
        g = powerlaw_bipartite(80, 40, 350, seed=2)
        wed = csr.build_wedges(g)
        bl = D.shard_tip_pairs(wed, wed.pair_butterflies0(), 8,
                               aligned=True)
        fn = D.make_cd_round_tip_csr(mesh2, ("grp", "loc"), g.n_u)
        txt = fn.lower(jnp.zeros((g.n_u + 1,), bool),
                       jnp.zeros((g.n_u + 1,), jnp.int32),
                       jnp.asarray(bl["dst"]), jnp.asarray(bl["src"]),
                       jnp.asarray(bl["bf"])).compile().as_text()
        n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
        assert n == 2, n
        flat = txt.replace(" ", "")
        assert "{{0,1,2,3},{4,5,6,7}}" in flat
        assert "{{0,4},{1,5},{2,6},{3,7}}" in flat
        for seed in (0, 1, 2):
            g = random_bipartite(16, 12, 48, seed=seed)
            want = ref.bup_tip_ref(g, "u")
            th, _ = D.distributed_tip_decomposition(
                g, mesh2, axis=("grp", "loc"), side="u", P_parts=4,
                engine="csr", aligned=True)
            assert np.array_equal(th, want), seed
        print("OK", n)
    """)
    assert "OK" in out


def test_hierarchical_cd_single_device_degenerate():
    """make_peel_mesh_2d(1) degenerates to a (1, 1) mesh; the staged
    psum pair is a no-op and θ still matches the single-device csr
    engine."""
    out = _run("""
        import numpy as np
        from repro.core.graph import powerlaw_bipartite
        from repro.core.distributed import distributed_wing_decomposition
        from repro.core.peel import wing_decomposition
        from repro.launch.mesh import make_peel_mesh_2d
        mesh2 = make_peel_mesh_2d(1)
        assert mesh2.devices.shape == (1, 1), mesh2.devices.shape
        g = powerlaw_bipartite(100, 50, 420, seed=5)
        theta, stats = distributed_wing_decomposition(
            g, mesh2, axis=("grp", "loc"), P_parts=6, engine="csr",
            pair_aligned=True)
        ref_theta = wing_decomposition(g, P=6, engine="csr").theta
        assert np.array_equal(theta, ref_theta)
        assert stats["n_dev"] == 1
        print("OK")
    """, n_dev=1)
    assert "OK" in out


def test_hierarchical_cd_512dev_two_staged_allreduces():
    """Production-mesh shape: make_peel_mesh_2d(512) → 16 groups x 32
    local devices; the pair-aligned CD round lowers to exactly two
    staged all-reduces whose replica groups are the 32-wide local rings
    ({0,...,31}, ...) and the 16-wide cross-group combs ({0,32,64,...})
    — the same lowering `launch.peel --dryrun` asserts."""
    out = _run("""
        import numpy as np, jax
        import jax.numpy as jnp
        from repro.core.graph import powerlaw_bipartite
        from repro.core import csr
        from repro.core import distributed as D
        from repro.launch.mesh import make_peel_mesh_2d
        mesh2 = make_peel_mesh_2d(512)
        assert mesh2.devices.shape == (16, 32), mesh2.devices.shape
        g = powerlaw_bipartite(100, 50, 500, seed=1)
        wed = csr.build_wedges(g)
        packed = D.shard_wedges_pair_aligned(wed, 512)
        fn = D.make_cd_round_csr_pair_aligned(
            mesh2, ("grp", "loc"), packed["Pmax"], g.m)
        peeled = jnp.zeros((g.m + 1,), bool)
        sup = jnp.zeros((g.m + 1,), jnp.int32)
        txt = fn.lower(peeled, jnp.asarray(packed["alive"]),
                       jnp.asarray(packed["W0"]), sup,
                       jnp.asarray(packed["we1"]), jnp.asarray(packed["we2"]),
                       jnp.asarray(packed["wp"])).compile().as_text()
        n = txt.count("all-reduce(") + txt.count("all-reduce-start(")
        assert n == 2, n
        flat = txt.replace(" ", "")
        assert "{0,1,2,3" in flat, "missing 32-wide local stage"
        assert "{0,32,64," in flat, "missing 16-wide cross-group stage"
        print("OK", n)
    """, n_dev=512)
    assert "OK" in out


def test_obs_off_cd_pair_aligned_jaxpr_byte_identical(obs_golden):
    """Zero-overhead-off at mesh scale: the one-psum pair-aligned CD
    round jaxpr (8 devices) re-derived with telemetry disabled equals
    the pre-instrumentation golden byte-for-byte.  CD instrumentation
    is host-side span bookkeeping around ``cd_step`` — the shard_map
    program itself must be untouched."""
    rec, golden = obs_golden
    out = _run(rec.CD_PAIR_ALIGNED_SRC)
    assert out.strip() == golden["cd_pair_aligned_8dev"]
