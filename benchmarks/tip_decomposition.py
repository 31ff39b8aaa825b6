"""Table 4 reproduction: tip decomposition — time, traversal work and ρ
for both vertex sets of each proxy dataset, across the dense and csr
engines (the csr rows are the entity-agnostic-core instantiation that
scales past the dense wall; distributed tip csr scaling lives in
``benchmarks.scaling`` as the ``dev{n}.tip_csr``/``.tip_aligned``
A/B)."""
from __future__ import annotations

import numpy as np

from repro.core import ref
from repro.core.graph import paper_proxy_dataset
from repro.core.peel import tip_decomposition

from .common import emit, timed


def run(small: bool = True):
    names = ["di_af", "fr"] if small else [
        "di_af", "fr", "di_st", "it", "digg", "lj"]
    for name in names:
        g = paper_proxy_dataset(name)
        for side in ("u", "v"):
            res, t = timed(tip_decomposition, g, side=side, P=12)
            s = res.stats
            emit(f"tip.{name}{side.upper()}.pbng", t,
                 rho=s.rho_cd + s.rho_fd_max, rho_cd=s.rho_cd,
                 rho_parb=s.rho_fd_total, recounts=s.recounts,
                 side=s.side,
                 sync_reduction=round(s.sync_reduction, 1))

            # csr engine: device-resident FD (one while_loop per
            # partition) vs the single-dispatch vmapped Phase 2 — the
            # same A/B the wing rows carry, now for the tip side of the
            # unified core.  repeat=2 so best-of excludes one-time
            # while_loop compilation.
            res_c, t_c = timed(
                tip_decomposition, g, side=side, P=12, engine="csr",
                repeat=2)
            assert np.array_equal(res_c.theta, res.theta), (name, side)
            res_v, t_v = timed(
                tip_decomposition, g, side=side, P=12, engine="csr",
                fd_driver="vmapped", repeat=2)
            assert np.array_equal(res_v.theta, res.theta), (name, side)
            assert res_v.stats.rho_fd_total == res_c.stats.rho_fd_total
            sc = res_c.stats
            emit(f"tip.{name}{side.upper()}.pbng_csr", t_c,
                 engine="csr", fd_driver="device", side=sc.side,
                 updates=sc.updates, rho_cd=sc.rho_cd,
                 sync_reduction=round(sc.sync_reduction, 1))
            emit(f"tip.{name}{side.upper()}.pbng_csr_vmapped", t_v,
                 engine="csr", fd_driver="vmapped", side=side,
                 rho_fd_max=res_v.stats.rho_fd_max,
                 vs_device=round(t_v / max(t_c, 1e-9), 2))
            if g.m <= 3000:
                _, t_bup = timed(ref.bup_tip_ref, g, side)
                emit(f"tip.{name}{side.upper()}.bup", t_bup,
                     kind="sequential-oracle")

    # the vmapped driver on one synthetic graph
    from repro.core.graph import powerlaw_bipartite

    gp = powerlaw_bipartite(60, 40, 260, seed=7)
    _, t_v = timed(
        tip_decomposition, gp, side="u", P=6, engine="csr",
        fd_driver="vmapped", repeat=2)
    emit("tip.pl60U.pbng_csr_vmapped", t_v, engine="csr",
         fd_driver="vmapped", side="u")


if __name__ == "__main__":
    run(small=False)
