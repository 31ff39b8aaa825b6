"""The control: the plain reference's coarse peel put in the place of the
system's peel, driven through whole runs of a cell, which then have to
come out not correct.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 8

``coarse=2`` breaks the configuration's guarantee of exact numbers: each
round peels every support below k + 2 at level k, as a CD range would
without FD.  The system's own peel still runs (its counters and set-up
are the cell's), its θ is replaced by the control's, and the system
builds and serves the forests from that θ.  Every seed runs in this one
process; each prints the numbers compared beside their limits.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
COARSE = 2


def install(patch=setattr) -> None:
    """Replace the θ of every wing and tip decomposition with the
    reference's coarse θ, and keep the control's served forests apart
    (``patch``: how to set an attribute, e.g. a test's monkeypatch)."""
    from repro.core import peel

    from bench import check, reference, serve

    real_wing, real_tip = peel.wing_decomposition, peel.tip_decomposition

    def wing(g, *a, **kw):
        res = real_wing(g, *a, **kw)
        e = np.asarray(g.edges)
        theta = reference.wing_numbers(g.n_u, g.n_v, e, coarse=COARSE)
        emap = check.entity_map("wing", e, reference.canonical_edges(e))
        res.theta = theta[emap].astype(res.theta.dtype)
        return res

    def tip(g, side="u", *a, **kw):
        res = real_tip(g, side, *a, **kw)
        theta = reference.tip_numbers(g.n_u, g.n_v, np.asarray(g.edges),
                                      side, coarse=COARSE)
        res.theta = theta.astype(res.theta.dtype)
        return res

    real_dir = serve.Driver._artifact_dir
    patch(peel, "wing_decomposition", wing)
    patch(peel, "tip_decomposition", tip)
    patch(serve.Driver, "_artifact_dir", lambda self: real_dir(self).with_name(
        real_dir(self).name + ".control"))


def main(argv=None) -> int:
    """The command line; returns the exit code."""
    import argparse

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    install()
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           t0=time.perf_counter())
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              correct=out["correct"], failed=out["failed"],
                              checks=out["checks"])), flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and Path(sys.path[0]).resolve() == Path(__file__).parent:
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
