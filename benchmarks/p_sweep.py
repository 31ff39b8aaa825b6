"""Fig. 5 reproduction: execution time vs number of partitions P.

The paper's claim: runtime is robust (within ~2× of optimal) across a
wide range of P; small P starves FD parallelism, large P adds CD rounds.
"""
from __future__ import annotations

from repro.core.graph import paper_proxy_dataset, powerlaw_bipartite
from repro.core.peel import wing_decomposition

from .common import emit, timed


def run(small: bool = True):
    name = "fr"
    g = paper_proxy_dataset(name)
    ps = (2, 8, 32) if small else (1, 2, 4, 8, 16, 32, 64, 128)
    for P in ps:
        res, t = timed(wing_decomposition, g, P=P, engine="beindex")
        s = res.stats
        emit(f"psweep.{name}.P{P}", t, rho_cd=s.rho_cd,
             rho_fd_max=s.rho_fd_max, parts=s.p_effective,
             updates=s.updates)
        # csr engine at the same P: device-resident FD vs host-loop FD —
        # the sync-reduction claim with the engine's OWN rho (not the
        # beindex run's), plus the wall-clock win of the while_loop FD
        res_d, t_d = timed(wing_decomposition, g, P=P, engine="csr",
                           repeat=2)
        res_h, t_h = timed(
            wing_decomposition, g, P=P, engine="csr", fd_driver="host",
            repeat=2)
        res_v, t_v = timed(
            wing_decomposition, g, P=P, engine="csr",
            fd_driver="vmapped", repeat=2)
        sd = res_d.stats
        emit(f"psweep.{name}.P{P}.csr", t_d, rho_cd=sd.rho_cd,
             rho_fd_max=sd.rho_fd_max,
             sync_reduction=round(sd.sync_reduction, 1),
             fd_driver="device",
             speedup_vs_hostfd=round(t_h / max(t_d, 1e-9), 2))
        emit(f"psweep.{name}.P{P}.csr_hostfd", t_h,
             rho_cd=res_h.stats.rho_cd, fd_driver="host")
        # the P-sensitivity of the single-dispatch FD: lock-step cost
        # grows with partition-drain imbalance, dispatch savings with P
        emit(f"psweep.{name}.P{P}.csr_vmapped", t_v,
             rho_fd_max=res_v.stats.rho_fd_max, fd_driver="vmapped",
             vs_device=round(t_v / max(t_d, 1e-9), 2))
    # the vmapped driver's P-sensitivity on the pl60 proxy
    gp = powerlaw_bipartite(60, 40, 260, seed=7)
    for P in ps:
        res_v, t_v = timed(wing_decomposition, gp, P=P, engine="csr",
                           fd_driver="vmapped", repeat=2)
        emit(f"psweep.pl60.P{P}.csr_vmapped", t_v,
             rho_fd_max=res_v.stats.rho_fd_max, fd_driver="vmapped",
             parts=res_v.stats.p_effective)


if __name__ == "__main__":
    run(small=False)
