"""Run one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks
for.  The run refuses to start without them (exit 3, no result).  It
turns on JAX's persistent compilation cache (``.jax_cache`` in the
checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set), makes the cell's
inputs from ``--seed``, warms up every shape the window uses, measures
for ``--seconds``, then checks what the window produced against the
plain reference in ``bench/reference.py``.

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; untraced, ``unbounded`` holds numbers reported without
a bound (the serving cell's ``query_p99_ms``); ``checks`` comes last and
gives each number compared beside its limit.  The same numbers are the last lines of standard
error.  Everything else goes to standard error before them.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(msg: str) -> None:
    """A progress line on standard error."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _metric(entry: dict, value) -> dict:
    return dict(value=value, unit=entry["unit"])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_tpu: bool = True,
             t0: float = None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    from bench import device, spec, tracing

    t0 = T0 if t0 is None else t0
    bm = spec.load(root)
    cell = spec.cell(bm, workload)
    cfg = spec.config(bm, cell["config"], root)
    traffic = spec.traffic(cell["traffic"], root)
    e2e = spec.metrics_for(bm, "end_to_end", workload)
    layer = spec.metrics_for(bm, "per_layer", workload)
    readers = {m["name"]: spec.reader(m["name"], root) for m in layer}

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = device.info(cell["chips"], require_tpu=require_tpu)
    if require_tpu:
        hbm = device.peaks(dev["kind"])["hbm_bytes"]
    log(f"{workload}: {dev}, jax {jax.__version__}, cache {cache_dir}")
    compiles = device.CompileCounter()

    drv = importlib.import_module(f"bench.{traffic['driver']}").Driver(
        cfg, traffic, seed, log, root)
    spans = None
    if trace:
        spans = tracing.Spans()
        drv.wrap(spans)
    try:
        drv.setup()
        before = compiles.snapshot()
        t_w = time.perf_counter()
        setup_s = t_w - t0
        log(f"set-up {setup_s:.3f} s; {before}")
        win = drv.window(seconds, trace)
        after = compiles.snapshot()
        log("inside the window: " + ", ".join(
            f"{k} {after[k] - before[k]}" for k in after))
    finally:
        if spans is not None:
            spans.restore()
    dev["memory_peak_bytes"] = device.memory_peak_bytes(cell["chips"])
    if require_tpu and dev["memory_peak_bytes"] is not None:
        log(f"memory peak {dev['memory_peak_bytes']} B = "
            f"{100 * dev['memory_peak_bytes'] / hbm:.4f} % of HBM")

    t_c = time.perf_counter()
    checks, failed = drv.check()
    log(f"check against the reference {time.perf_counter() - t_c:.3f} s")
    correct = all(lim is None or val <= lim
                  for val, lim in checks.values())

    out = dict(correct=correct, attempted=win["attempted"], failed=failed)
    if trace:
        ctx = drv.layer_context()
        ctx["trace"] = win["trace"]
        metrics = {}
        for m in layer:
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = _metric(m, v)
        out["metrics"] = metrics
        red = win["trace"]
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["device"] = dev
        out["breakdown"] = dict(device_ops=red["device_ops"],
                                idle_gaps=red["idle_gaps"])
    else:
        values = dict(win["values"], setup_s=setup_s)
        out["metrics"] = {m["name"]: _metric(m, values[m["name"]])
                          for m in e2e}
        out["device"] = dev
        if win.get("unbounded"):
            out["unbounded"] = win["unbounded"]
    out["checks"] = {k: dict(value=v, limit=lim)
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None) -> int:
    """The command line; returns the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no system under test: {ROOT / 'src' / 'repro'} is missing")
        return 2
    from bench.device import NoChip

    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and Path(sys.path[0]).resolve() == Path(__file__).parent:
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
