#!/usr/bin/env python
"""Record the CPU goldens that ``chip_smoke.py`` checks the chip against
(``chip_smoke.json``).

Two seeded skewed graphs (:data:`GRAPHS`) are peeled through the
launcher's own path (``launch/peel.py``'s ``run``: ``--engine csr`` with
the default FD driver) for wing and for tip (side u); each θ is recorded
as the sha256 of its int64 vector.  ``peel`` is phase (b)'s graph;
``forest`` is the graph of phase (c) and of the four-chip run, and the
dense-subgraph forest built from each of its θ is recorded as
:func:`record_stream_goldens.forest_digest` over every packed-forest
array.  Everything is integer peeling, so the digests are
machine-independent: a TPU run of the same path must reproduce them bit
for bit.

Run on the CPU from the repo root (about three minutes, under 4 GB of
host memory)::

    JAX_PLATFORMS=cpu PYTHONPATH=src python tests/goldens/record_chip_smoke.py

Re-record only when peel or hierarchy semantics intentionally change.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
GOLDEN_PATH = os.path.join(HERE, "chip_smoke.json")
sys.path.insert(0, HERE)

from record_stream_goldens import forest_digest  # noqa: E402

# The power-law generator at the degree skew the paper targets (alpha
# 0.7, |U|:|V| = 4:1), cut from 200k x 50k x 1M (1.59e8 V-centred
# wedges), whose wing peel did not finish within 700 s on one v5e:
#   peel   1/5:  16,483,275 wedges, max degrees 1,404 (U) / 2,740 (V);
#   forest 1/20:  2,386,826 wedges.  The forest is not built from the
#          peel graph: there the wing label program fits the chip's HBM
#          only at level_block 1 (~0.6 KB per incidence at any block
#          >= 2, ~20 GiB for its 33M incidences), and that build alone
#          takes longer on one v5e than phases (a) and (b) leave of the
#          smoke run's 1200 s.
GRAPHS = dict(
    peel=dict(n_u=40_000, n_v=10_000, m=200_000, alpha=0.7, seed=0),
    forest=dict(n_u=10_000, n_v=2_500, m=50_000, alpha=0.7, seed=0),
)
KINDS = ("wing", "tip")


def launcher_args(kind: str, graph: dict) -> list:
    """The ``launch/peel.py`` command line of one phase-(b) peel."""
    return ["--kind", kind, "--engine", "csr", "--side", "u",
            "--n-u", str(graph["n_u"]), "--n-v", str(graph["n_v"]),
            "--m", str(graph["m"]), "--alpha", str(graph["alpha"]),
            "--seed", str(graph["seed"])]


def theta_sha(theta) -> str:
    return hashlib.sha256(
        np.asarray(theta, dtype=np.int64).tobytes()).hexdigest()


def v_centred_wedges(g) -> int:
    """Σ_v C(d_v, 2): the wedge count ``core.csr.build_wedges`` holds."""
    _, dv = g.degrees()
    dv = dv.astype(np.int64)
    return int((dv * (dv - 1) // 2).sum())


def main() -> None:
    from repro.hierarchy import build_hierarchy
    from repro.launch import peel

    out = {}
    for name, graph in GRAPHS.items():
        rec = out[name] = dict(graph=graph)
        for kind in KINDS:
            r = peel.run(peel.build_parser().parse_args(
                launcher_args(kind, graph)))
            g = r["graph"]
            rec[kind] = dict(theta_sha256=theta_sha(r["theta"]))
            rec.update(m=int(g.m), n_wedges=v_centred_wedges(g))
            if name == "forest":
                h = build_hierarchy(g, r["result"], kind=kind, side="u")
                rec[kind].update(forest=forest_digest(h), n_nodes=h.n_nodes)
            print(name, kind, rec[kind], flush=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    main()
