"""Fig. 8/11 analogue: multi-device scaling of distributed PBNG.

One physical core backs all host devices here, so wall-clock speedup is
not observable; we report the *structural* scaling quantities instead:
per-device work (link-shard size, FD partitions per device) and the
synchronization count, which is device-count-invariant — exactly the
property that gave the paper its 19.7× on real cores.  Wall time is
reported for completeness.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import emit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = """
import json, time
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.graph import powerlaw_bipartite
from repro.core.beindex import build_beindex
from repro.core.distributed import distributed_wing_decomposition
n = {n_dev}
mesh = Mesh(np.array(jax.devices()).reshape(n), ("peel",))
g = powerlaw_bipartite(300, 150, 1400, seed=4)
be = build_beindex(g)
t0 = time.time()
theta, stats = distributed_wing_decomposition(g, mesh, P_parts=32, be=be)
dt = time.time() - t0
stats.update(wall_s=dt, links_per_dev=-(-be.n_links // n),
             theta_sum=int(theta.sum()))
print(json.dumps(stats))
"""


_SCRIPT_CSR = """
import json, time
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.graph import powerlaw_bipartite
from repro.core.distributed import distributed_wing_decomposition
n = {n_dev}
mesh = Mesh(np.array(jax.devices()).reshape(n), ("peel",))
g = powerlaw_bipartite(300, 150, 1400, seed=4)
out = {{}}
for pal in (False, True):
    t0 = time.time()
    theta, stats = distributed_wing_decomposition(
        g, mesh, P_parts=32, engine="csr", pair_aligned=pal)
    stats.update(wall_s=time.time() - t0, theta_sum=int(theta.sum()))
    out["pal" if pal else "wedge"] = stats
assert out["pal"]["theta_sum"] == out["wedge"]["theta_sum"]
print(json.dumps(out))
"""


_SCRIPT_TIP = """
import json, time
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.graph import powerlaw_bipartite
from repro.core.distributed import distributed_tip_decomposition
n = {n_dev}
mesh = Mesh(np.array(jax.devices()).reshape(n), ("peel",))
g = powerlaw_bipartite(300, 150, 1400, seed=4)
out = {{}}
for aligned in (False, True):
    t0 = time.time()
    theta, stats = distributed_tip_decomposition(
        g, mesh, side="u", P_parts=32, engine="csr", aligned=aligned)
    stats.update(wall_s=time.time() - t0, theta_sum=int(theta.sum()))
    out["aligned" if aligned else "rr"] = stats
assert out["aligned"]["theta_sum"] == out["rr"]["theta_sum"]
print(json.dumps(out))
"""


_SCRIPT_HIER = """
import json, time
import numpy as np, jax
from repro.core.graph import powerlaw_bipartite
from repro.core.distributed import distributed_wing_decomposition
from repro.launch.mesh import make_peel_mesh_2d
n = {n_dev}
mesh2 = make_peel_mesh_2d(n)
g = powerlaw_bipartite(300, 150, 1400, seed=4)
t0 = time.time()
theta, stats = distributed_wing_decomposition(
    g, mesh2, axis=("grp", "loc"), P_parts=32, engine="csr",
    pair_aligned=True)
stats.update(wall_s=time.time() - t0, theta_sum=int(theta.sum()),
             groups=int(mesh2.devices.shape[0]),
             loc=int(mesh2.devices.shape[1]))
print(json.dumps(stats))
"""


def run(small: bool = True):
    devs = (1, 4) if small else (1, 2, 4, 8, 16)
    base = None
    tip_base = None
    for n in devs:
        env = dict(os.environ)
        # virtual host devices for structure rows: the children stay off
        # any accelerator (the parent benchmark process may hold it)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        out = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_SCRIPT.format(n_dev=n))],
            env=env, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        stats = json.loads(out.stdout.strip().splitlines()[-1])
        if base is None:
            base = stats["theta_sum"]
        assert stats["theta_sum"] == base, "device count changed results!"
        emit(f"scaling.wing.dev{n}", stats["wall_s"],
             rho_cd=stats["rho_cd"], links_per_dev=stats["links_per_dev"],
             parts_per_dev=-(-stats["n_parts"] // n))
        # csr CD sharding A/B: round-robin wedge shards (two psums per
        # round) vs pair-aligned shards (ONE psum) — report.py renders
        # the cd.pair_aligned/wedge ratio row from these
        out = subprocess.run(
            [sys.executable, "-c",
             textwrap.dedent(_SCRIPT_CSR.format(n_dev=n))],
            env=env, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        both = json.loads(out.stdout.strip().splitlines()[-1])
        emit(f"scaling.wing.dev{n}.csr", both["wedge"]["wall_s"],
             rho_cd=both["wedge"]["rho_cd"], psums_per_round=2,
             cd_sharding="wedge")
        emit(f"scaling.wing.dev{n}.csr_pal", both["pal"]["wall_s"],
             rho_cd=both["pal"]["rho_cd"], psums_per_round=1,
             cd_sharding="pair_aligned")
        # hierarchical-collective A/B: the SAME one logical psum staged
        # over a 2-D ("grp", "loc") mesh — two all-reduces with nested
        # replica groups vs the flat ring (groups degenerate to 1 below
        # 4 devices).  On forced host devices the staging is pure
        # overhead; the row certifies theta-invariance and tracks the
        # structural cost.  report.py renders cd.hier/flat from these.
        out = subprocess.run(
            [sys.executable, "-c",
             textwrap.dedent(_SCRIPT_HIER.format(n_dev=n))],
            env=env, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        hier = json.loads(out.stdout.strip().splitlines()[-1])
        assert hier["theta_sum"] == both["pal"]["theta_sum"], \
            "hierarchical mesh changed results!"
        emit(f"scaling.wing.dev{n}.csr_pal_hier", hier["wall_s"],
             rho_cd=hier["rho_cd"], psums_per_round=1,
             staged_allreduces=2, cd_sharding="pair_aligned",
             mesh=f"{hier['groups']}x{hier['loc']}")
        # tip csr CD sharding A/B: round-robin vs vertex-aligned pair
        # entries — both pay ONE psum per round (pair butterflies are
        # static), so the A/B isolates the greedy balance; report.py
        # renders the cd.aligned/roundrobin ratio row from these
        out = subprocess.run(
            [sys.executable, "-c",
             textwrap.dedent(_SCRIPT_TIP.format(n_dev=n))],
            env=env, capture_output=True, text=True, timeout=900)
        assert out.returncode == 0, out.stderr[-2000:]
        tips = json.loads(out.stdout.strip().splitlines()[-1])
        if tip_base is None:
            tip_base = tips["rr"]["theta_sum"]
        assert tips["rr"]["theta_sum"] == tip_base, \
            "device count changed tip results!"
        emit(f"scaling.tip.dev{n}.tip_csr", tips["rr"]["wall_s"],
             rho_cd=tips["rr"]["rho_cd"], psums_per_round=1,
             cd_sharding="pair", side="u")
        emit(f"scaling.tip.dev{n}.tip_aligned", tips["aligned"]["wall_s"],
             rho_cd=tips["aligned"]["rho_cd"], psums_per_round=1,
             cd_sharding="vertex_aligned", side="u")


if __name__ == "__main__":
    run(small=False)
