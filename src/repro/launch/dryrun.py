"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh)
cell on placeholder devices and extract roofline inputs.

MUST be the very first lines — jax locks the device count on first init:
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_config  # noqa: E402
from repro.launch.hlo_analysis import collective_bytes  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from jax import set_mesh  # noqa: E402
import repro.models as M  # noqa: E402
from repro.models.model import SHAPE_SETS  # noqa: E402
from repro.sharding import (  # noqa: E402
    batch_shardings,
    cache_shardings,
    param_shardings,
)
from repro.train.optimizer import AdamWConfig, OptState, abstract_opt_state  # noqa: E402
from repro.train.train_step import TrainConfig, make_train_step  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def dryrun_cell(arch: str, shape: str, multi_pod: bool = False,
                microbatches: int = 1, verbose: bool = True,
                extra_tags: str = "",
                cfg_overrides: Optional[Dict] = None) -> Dict:
    """Lower + compile one cell; returns the roofline record."""
    import dataclasses as _dc
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    ok, why = M.shape_applicable(cfg, shape)
    if not ok:
        return dict(arch=arch, shape=shape, multi_pod=multi_pod,
                    status="skipped", reason=why)

    mesh = make_production_mesh(multi_pod=multi_pod)
    info = SHAPE_SETS[shape]
    axes = M.logical_axes(cfg)
    pabs = M.abstract_params(cfg, jnp.bfloat16)
    p_sh = param_shardings(axes, pabs, mesh)
    t0 = time.time()
    ctx = set_mesh(mesh)  # so constrain() sees axis names
    ctx.__enter__()

    if info["kind"] == "train":
        oabs = abstract_opt_state(pabs)
        o_sh = OptState(mu=p_sh, nu=p_sh,
                        step=NamedSharding(mesh, P()))
        batch_abs = M.input_specs(cfg, shape)
        b_sh = batch_shardings(batch_abs, mesh)
        step = make_train_step(
            cfg, TrainConfig(microbatches=microbatches,
                             opt=AdamWConfig()))
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, None),
        )
        lowered = jitted.lower(pabs, oabs, batch_abs)
    elif info["kind"] == "prefill":
        batch_abs = M.input_specs(cfg, shape)
        b_sh = batch_shardings(batch_abs, mesh)

        def pf(params, batch):
            return M.prefill(params, batch["tokens"], cfg,
                             positions=batch.get("positions"),
                             frames=batch.get("frames"))

        jitted = jax.jit(pf, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(pabs, batch_abs)
    else:  # decode
        spec = M.input_specs(cfg, shape)
        cache_abs = spec["cache"]
        c_sh = cache_shardings(cache_abs, mesh, cfg)
        tok_sh = batch_shardings(
            dict(token=spec["token"]), mesh)["token"]

        def dec(params, cache, token, length):
            return M.serve_step(params, cache, token, length, cfg)

        jitted = jax.jit(
            dec,
            in_shardings=(p_sh, c_sh, tok_sh, NamedSharding(mesh, P())),
            out_shardings=(None, c_sh),
        )
        lowered = jitted.lower(pabs, cache_abs, spec["token"],
                               spec["length"])

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ctx.__exit__(None, None, None)

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    rec = dict(
        arch=arch, shape=shape, multi_pod=multi_pod, status="ok",
        kind=info["kind"],
        n_devices=int(mesh.devices.size),
        flops=float(cost.get("flops", -1)),
        bytes_accessed=float(cost.get("bytes accessed", -1)),
        collective_bytes=coll,
        time_lower_s=round(t_lower, 1),
        time_compile_s=round(t_compile, 1),
        tags=extra_tags,
    )
    for k in ("bytes accessed0{}", "bytes accessed1{}",
              "bytes accessedout{}"):
        if k in cost:
            rec[k.replace(" ", "_").replace("{}", "")] = float(cost[k])
    if mem is not None:
        rec["mem"] = dict(
            argument_bytes=int(getattr(mem, "argument_size_in_bytes", -1)),
            output_bytes=int(getattr(mem, "output_size_in_bytes", -1)),
            temp_bytes=int(getattr(mem, "temp_size_in_bytes", -1)),
            code_bytes=int(
                getattr(mem, "generated_code_size_in_bytes", -1)),
        )
    if verbose:
        tb = rec.get("mem", {}).get("temp_bytes", -1)
        print(f"[dryrun] {arch:18s} {shape:12s} "
              f"{'2pod' if multi_pod else '1pod'} OK "
              f"flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
              f"coll={sum(coll.values()):.3e}B temp={tb:.3e}B "
              f"compile={t_compile:.0f}s", flush=True)
    return rec


def run_all(out_path: str, multi_pod_values=(False, True),
            archs=None, shapes=None, resume=True,
            microbatches: int = 1):
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    results = []
    done = set()
    if resume and os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
        done = {(r["arch"], r["shape"], r["multi_pod"],
                 r.get("tags", "")) for r in results}
    tags = f"mb{microbatches}" if microbatches > 1 else ""
    for arch in (archs or ARCHS):
        for shape in (shapes or list(SHAPE_SETS)):
            for mp in multi_pod_values:
                key = (arch, shape, mp, tags)
                if key in done:
                    continue
                try:
                    rec = dryrun_cell(arch, shape, multi_pod=mp,
                                      microbatches=microbatches,
                                      extra_tags=tags)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = dict(arch=arch, shape=shape, multi_pod=mp,
                               status="error", error=str(e)[-2000:],
                               tags=tags)
                    print(f"[dryrun] {arch} {shape} mp={mp} FAILED: "
                          f"{type(e).__name__}", flush=True)
                results.append(rec)
                with open(out_path, "w") as f:
                    json.dump(results, f, indent=1)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    out = args.out or os.path.abspath(
        os.path.join(RESULTS_DIR, "results.json"))
    if args.arch and args.shape:
        rec = dryrun_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                          microbatches=args.microbatches)
        print(json.dumps(rec, indent=2))
        return
    mp_vals = (False, True)
    if args.single_pod_only:
        mp_vals = (False,)
    if args.multi_pod_only:
        mp_vals = (True,)
    archs = [args.arch] if args.arch else None
    shapes = [args.shape] if args.shape else None
    run_all(out, mp_vals, archs, shapes,
            microbatches=args.microbatches)


if __name__ == "__main__":
    main()
