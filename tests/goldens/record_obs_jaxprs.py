#!/usr/bin/env python
"""Record the telemetry-off reference jaxprs for the observability layer.

The obs subsystem (``src/repro/obs/``) carries a hard guarantee:
**telemetry off produces byte-identical jaxprs** — the counter-ring
instrumentation threaded through the FD loop carries must be a
trace-time branch that, when disabled (the default), leaves the traced
program literally unchanged.  This script records the reference texts
the assertion suites compare against:

* fused FD (wing + tip): the whole cascade, body = one ``pallas_call``;
* vmapped FD (wing + tip): the whole Phase 2 as ONE ``while_loop``;
* one-psum pair-aligned CD round (8-device shard_map, subprocess);
* the multiserve batched dispatch (loop/collective-free).

It was run ONCE at the pre-instrumentation tree to produce
``tests/goldens/obs_jaxprs.json``; the suites re-derive the same
jaxprs from the instrumented tree (telemetry disabled) and assert
byte-equality (``tests/test_fused_fd.py``, ``tests/test_multiserve.py``,
``tests/test_core_distributed.py``).  Re-record only when a jaxpr is
*intentionally* changed on the default path:

    PYTHONPATH=src python tests/goldens/record_obs_jaxprs.py

The case builders below are imported by the assertion suites so the
recorded and re-derived jaxprs come from identical inputs.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
GOLDEN_PATH = os.path.join(HERE, "obs_jaxprs.json")

# the 8-device subprocess case: the pair-aligned one-psum CD round.
# Kept as source so the recorder and test_core_distributed.py run the
# EXACT same program (the test pipes it through its own _run helper).
CD_PAIR_ALIGNED_SRC = """
    import numpy as np, jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.graph import powerlaw_bipartite
    from repro.core import csr
    from repro.core import distributed as D
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("peel",))
    g = powerlaw_bipartite(80, 40, 350, seed=2)
    wed = csr.build_wedges(g)
    packed = D.shard_wedges_pair_aligned(wed, 8)
    fn = D.make_cd_round_csr_pair_aligned(
        mesh, "peel", packed["Pmax"], g.m)
    peeled = jnp.zeros((g.m + 1,), bool)
    sup = jnp.zeros((g.m + 1,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(fn)(
        peeled, jnp.asarray(packed["alive"]), jnp.asarray(packed["W0"]),
        sup, jnp.asarray(packed["we1"]), jnp.asarray(packed["we2"]),
        jnp.asarray(packed["wp"])))
    print(jaxpr.strip())
"""


def _wing_pack():
    import numpy as np

    from repro.core import csr
    from repro.core.distributed import pack_fd_partitions_csr
    from repro.core.graph import random_bipartite
    from repro.core.peel import wing_decomposition

    g = random_bipartite(30, 24, 140, seed=0)
    wed = csr.build_wedges(g)
    res = wing_decomposition(g, P=4, engine="csr")
    n_parts = int(res.part.max()) + 1
    slotted = pack_fd_partitions_csr(
        wed, res.part, res.support_init, n_parts, bucket=True, slots=True)
    R, _ = slotted["slot_sizes"]
    W_rows = np.zeros((n_parts, R), np.int32)
    w = min(R, slotted["W0"].shape[1])
    W_rows[:, :w] = slotted["W0"][:, :w]
    slotted["W_rows"] = W_rows
    flat = pack_fd_partitions_csr(
        wed, res.part, res.support_init, n_parts, bucket=True, flat=True)
    return slotted, flat


def _tip_pack():
    from repro.core import csr
    from repro.core.distributed import pack_fd_partitions_tip_csr
    from repro.core.graph import random_bipartite
    from repro.core.peel import tip_decomposition

    g = random_bipartite(30, 24, 140, seed=0)
    wed = csr.build_wedges(g)
    res = tip_decomposition(g, side="u", P=4, engine="csr")
    n_parts = int(res.part.max()) + 1
    stacked = pack_fd_partitions_tip_csr(
        wed, wed.pair_butterflies0(), res.part, res.support_init,
        n_parts, bucket=True, stacked=True)
    bucketed = pack_fd_partitions_tip_csr(
        wed, wed.pair_butterflies0(), res.part, res.support_init,
        n_parts, bucket=True)
    return stacked, bucketed


def fused_wing_jaxpr() -> str:
    import jax
    import jax.numpy as jnp

    from repro.core.peel import _fd_wing_fused_impl

    p, _ = _wing_pack()
    return str(jax.make_jaxpr(_fd_wing_fused_impl)(
        jnp.asarray(p["slot_e1"]), jnp.asarray(p["slot_e2"]),
        jnp.asarray(p["slot_valid"]), jnp.asarray(p["W_rows"]),
        jnp.asarray(p["mine"]), jnp.asarray(p["sup0"]))).strip()


def fused_tip_jaxpr() -> str:
    import jax
    import jax.numpy as jnp

    from repro.core.peel import _fd_tip_fused_impl

    p, _ = _tip_pack()
    return str(jax.make_jaxpr(_fd_tip_fused_impl)(
        jnp.asarray(p["st_pa"]), jnp.asarray(p["st_pb"]),
        jnp.asarray(p["st_bf"]), jnp.asarray(p["mine"]),
        jnp.asarray(p["sup0"]))).strip()


def vmapped_wing_jaxpr() -> str:
    import jax
    import jax.numpy as jnp

    from repro.core.peel import _fd_wing_vmapped

    _, p = _wing_pack()
    n_pairs = int(p["flat_W0"].shape[0])
    return str(jax.make_jaxpr(
        lambda *a: _fd_wing_vmapped(*a, n_pairs=n_pairs))(
        jnp.asarray(p["flat_we1"]), jnp.asarray(p["flat_we2"]),
        jnp.asarray(p["flat_wp"]), jnp.asarray(p["flat_alive0"]),
        jnp.asarray(p["flat_W0"]), jnp.asarray(p["mine"]),
        jnp.asarray(p["sup0"]))).strip()


def vmapped_tip_jaxpr() -> str:
    import jax
    import jax.numpy as jnp

    from repro.core.peel import _fd_tip_vmapped

    _, p = _tip_pack()
    return str(jax.make_jaxpr(_fd_tip_vmapped)(
        jnp.asarray(p["pa"]), jnp.asarray(p["pb"]),
        jnp.asarray(p["bf"]), jnp.asarray(p["mine"]),
        jnp.asarray(p["sup0"]))).strip()


def device_wing_jaxpr() -> str:
    """Per-partition wing FD while_loop on a fixed synthetic shape —
    the single launch that the compacting driver behind ``run_fd``
    (streaming's ``only=...`` re-runs included) must match, and that the
    timeline twin mirrors.  A jaxpr is a function of shapes and statics
    only, so no graph artifacts are needed."""
    import jax
    import jax.numpy as jnp

    from repro.core.peel import _fd_wing_device

    m, n_pairs, n_kept = 140, 64, 96
    mine = jnp.zeros((m,), bool)
    sup0 = jnp.zeros((m,), jnp.int32)
    alive = jnp.zeros((n_kept,), bool)
    W0 = jnp.zeros((n_pairs,), jnp.int32)
    we = jnp.zeros((n_kept,), jnp.int32)
    return str(jax.make_jaxpr(
        lambda *a: _fd_wing_device(*a, n_pairs=n_pairs, m=m))(
        mine, sup0, alive, W0, we, we, we)).strip()


def device_tip_jaxpr() -> str:
    """Per-partition tip FD while_loop on a fixed synthetic shape (the
    tip twin of :func:`device_wing_jaxpr`)."""
    import jax
    import jax.numpy as jnp

    from repro.core.peel import _fd_tip_device

    n, n_pairs = 30, 40
    mine = jnp.zeros((n,), bool)
    sup0 = jnp.zeros((n,), jnp.int32)
    pa = jnp.zeros((n_pairs,), jnp.int32)
    return str(jax.make_jaxpr(
        lambda *a: _fd_tip_device(*a, n=n))(
        mine, sup0, pa, pa, pa)).strip()


def multiserve_dispatch_jaxpr() -> str:
    """Dispatch jaxpr on a fixed synthetic bucket shape (the program is
    a function of shapes only, so no artifacts are needed)."""
    import jax
    import jax.numpy as jnp

    from repro.hierarchy import multiserve

    cap, n_pad, e_pad, J, batch = 4, 16, 16, 4, 64
    z2e = jnp.zeros((cap, e_pad), jnp.int32)
    z2n = jnp.zeros((cap, n_pad), jnp.int32)
    up = jnp.zeros((cap, n_pad, J), jnp.int32)
    z = jnp.zeros(batch, jnp.int32)
    return str(jax.make_jaxpr(
        lambda *x: multiserve._answer_batch_multi(*x, J=J))(
        z2e, z2e, z2n, z2n, z2n, up, z, z, z, z)).strip()


def cd_pair_aligned_jaxpr() -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CD_PAIR_ALIGNED_SRC)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-4000:])
    return out.stdout.strip()


CASES = {
    "fused_wing": fused_wing_jaxpr,
    "fused_tip": fused_tip_jaxpr,
    "vmapped_wing": vmapped_wing_jaxpr,
    "vmapped_tip": vmapped_tip_jaxpr,
    "device_wing": device_wing_jaxpr,
    "device_tip": device_tip_jaxpr,
    "multiserve_dispatch": multiserve_dispatch_jaxpr,
    "cd_pair_aligned_8dev": cd_pair_aligned_jaxpr,
}


def main() -> None:
    import jax

    golden = {"jax": jax.__version__, "jaxprs": {}}
    for name, fn in CASES.items():
        txt = fn()
        golden["jaxprs"][name] = txt
        print(f"[record-obs] {name}: {len(txt)} chars")
    with open(GOLDEN_PATH, "w") as f:
        json.dump(golden, f, indent=1)
    print(f"[record-obs] wrote {len(golden['jaxprs'])} jaxprs -> "
          f"{GOLDEN_PATH}")


if __name__ == "__main__":
    main()
