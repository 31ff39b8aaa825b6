"""Render EXPERIMENTS.md §Dry-run / §Roofline / §Benchmark tables from
the JSON results written by repro.launch.dryrun, repro.launch.roofline
and benchmarks.run (``BENCH_*.json``).

    PYTHONPATH=src python -m benchmarks.report [BENCH_csr.json ...]

The §Benchmarks section renders EVERY row of the given bench files —
including the FD/CD A/B ratio rows whose names contain ``/`` (e.g.
``wing.fr.fd.device/host``): a ``/`` in a row name is a ratio label,
not a path separator, and must never be filtered or split.  When no
bench file is passed, the committed baseline
(``benchmarks/baselines/BENCH_csr.json``) is rendered.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRY = os.path.join(ROOT, "experiments", "dryrun", "results.json")
ROOF = os.path.join(ROOT, "experiments", "roofline", "results.json")
BASELINE = os.path.join(ROOT, "benchmarks", "baselines", "BENCH_csr.json")

# A/B pairs synthesized from sibling time rows: (suffix_a, suffix_b,
# ratio label, name-prefix families the pair is benchmarked in).  The
# label becomes "<common prefix>.<label>" — names that deliberately
# contain '/' so a < 1.0 ratio reads "a is faster".  The families scope
# the half-missing-sibling 'n/a' marker to rows where the sibling is
# SUPPOSED to exist: a variant a family never benchmarks by design
# (e.g. tip has no hostfd row) must not drown real dropped-sibling gaps
# in structural noise.
AB_PAIRS = [
    ("pbng_csr", "pbng_csr_hostfd", "fd.device/host", ("wing.",)),
    ("pbng_csr_vmapped", "pbng_csr", "fd.vmapped/device",
     ("wing.", "tip.")),
    ("pbng_csr_vmapped_pallas", "pbng_csr_vmapped", "fd.pallas/segsum",
     ("wing.pl",)),
    ("csr", "csr_hostfd", "fd.device/host", ("psweep.",)),
    ("csr_vmapped", "csr", "fd.vmapped/device", ("psweep.",)),
    ("csr_pal", "csr", "cd.pair_aligned/wedge", ("scaling.",)),
    ("csr_pal_hier", "csr_pal", "cd.hier/flat", ("scaling.",)),
    ("tip_aligned", "tip_csr", "cd.aligned/roundrobin", ("scaling.",)),
]


def _fmt(x, unit=""):
    if x is None:
        return "-"
    if abs(x) >= 1e12:
        return f"{x/1e12:.2f}T{unit}"
    if abs(x) >= 1e9:
        return f"{x/1e9:.2f}G{unit}"
    if abs(x) >= 1e6:
        return f"{x/1e6:.2f}M{unit}"
    return f"{x:.3g}{unit}"


def dryrun_table() -> str:
    if not os.path.exists(DRY):
        return "_dry-run results not yet generated_\n"
    rs = json.load(open(DRY))
    lines = [
        "| arch | shape | mesh | status | HLO flops/dev (scan-once) | "
        "bytes/dev | collective B/dev | temp B/dev | compile s |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(rs, key=lambda r: (r["arch"], r["shape"],
                                       r.get("multi_pod", False))):
        mesh = "2×16×16" if r.get("multi_pod") else "16×16"
        if r["status"] == "ok":
            coll = sum(r["collective_bytes"].values())
            tmp = r.get("mem", {}).get("temp_bytes")
            lines.append(
                f"| {r['arch']} | {r['shape']} | {mesh} | ok | "
                f"{_fmt(r['flops'])} | {_fmt(r['bytes_accessed'])} | "
                f"{_fmt(coll)} | {_fmt(tmp)} | "
                f"{r['time_compile_s']} |")
        elif r["status"] == "skipped":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {mesh} | "
                f"SKIP ({r['reason'][:60]}…) | - | - | - | - | - |")
        else:
            lines.append(
                f"| {r['arch']} | {r['shape']} | {mesh} | ERROR | - | - "
                f"| - | - | - |")
    ok = sum(r["status"] == "ok" for r in rs)
    sk = sum(r["status"] == "skipped" for r in rs)
    er = len(rs) - ok - sk
    lines.append("")
    lines.append(f"**{ok} compiled, {sk} documented skips, {er} errors** "
                 f"(skips = long_500k on pure full-attention archs, "
                 f"per DESIGN.md §4).")
    return "\n".join(lines) + "\n"


def roofline_table() -> str:
    if not os.path.exists(ROOF):
        return "_roofline results not yet generated_\n"
    rs = json.load(open(ROOF))
    lines = [
        "| arch | shape | t_compute | t_memory | t_collective | "
        "bottleneck | MODEL/HLO flops | roofline frac |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in sorted(rs, key=lambda r: (r["arch"], r["shape"])):
        if r["status"] == "ok":
            lines.append(
                f"| {r['arch']} | {r['shape']} | "
                f"{r['t_compute_s']:.2e}s | {r['t_memory_s']:.2e}s | "
                f"{r['t_collective_s']:.2e}s | **{r['bottleneck']}** | "
                f"{r['useful_flop_ratio']:.2f} | "
                f"{r['roofline_fraction']:.2f} |")
        elif r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | - | - | - | "
                         f"skip | - | - |")
        else:
            lines.append(f"| {r['arch']} | {r['shape']} | - | - | - | "
                         f"ERROR | - | - |")
    return "\n".join(lines) + "\n"


def _fmt_us(us: float) -> str:
    if us >= 1e6:
        return f"{us / 1e6:.2f}s"
    if us >= 1e3:
        return f"{us / 1e3:.1f}ms"
    return f"{us:.0f}us"


def _escape(name: str) -> str:
    """Markdown-table safety: only '|' breaks a cell.  '/' is a legal
    row-name character (A/B ratio rows) and renders verbatim."""
    return name.replace("|", "\\|")


def ab_rows(rows: dict) -> list:
    """Synthesize the A/B ratio rows from sibling time rows.

    For every configured (a, b) suffix pair present with a common
    prefix — e.g. ``wing.fr.pbng_csr`` / ``wing.fr.pbng_csr_hostfd`` —
    emit ``(prefix.label, ratio)`` where ratio = t_a / t_b (< 1.0 means
    the numerator variant is faster).  A prefix where only ONE side of
    the pair exists still emits its row, with ratio ``None`` — the
    renderer marks it ``n/a`` so a dropped/renamed sibling is a visible
    gap in the report instead of a silently missing ratio."""
    out = []
    seen = set()
    for name, us in sorted(rows.items()):
        for suf_a, suf_b, label, families in AB_PAIRS:
            if not name.startswith(families):
                continue
            if name.endswith("." + suf_a):
                prefix = name[: -len(suf_a) - 1]
            elif name.endswith("." + suf_b):
                prefix = name[: -len(suf_b) - 1]
            else:
                continue
            key = f"{prefix}.{label}"
            if key in seen:
                continue
            seen.add(key)
            t_a = rows.get(f"{prefix}.{suf_a}")
            t_b = rows.get(f"{prefix}.{suf_b}")
            if t_a is not None and t_b is not None and t_b > 0:
                out.append((key, t_a / t_b))
            else:
                out.append((key, None))
    return out


def bench_table(paths: list) -> str:
    """§Benchmarks: every row of the bench JSONs (min-merged across
    files), then the synthesized A/B ratio rows.  No row is skipped —
    names containing '/' are ratio labels and render verbatim."""
    rows: dict = {}
    derived: dict = {}
    for path in paths:
        if not os.path.exists(path):
            return f"_bench results not found: {path}_\n"
        payload = json.load(open(path))
        for r in payload["rows"]:
            us = float(r["us_per_call"])
            if us < rows.get(r["name"], float("inf")):
                rows[r["name"]] = us
                derived[r["name"]] = {
                    k: v for k, v in r.items()
                    if k not in ("name", "us_per_call")
                }
    lines = ["| row | best-of time | derived |", "|---|---|---|"]
    for name in sorted(rows):
        extra = " ".join(f"{k}={v}" for k, v in derived[name].items())
        lines.append(
            f"| {_escape(name)} | {_fmt_us(rows[name])} | {extra} |")
    ab = ab_rows(rows)
    if ab:
        lines.append("")
        lines.append("### A/B ratios (t_a / t_b — < 1.0 ⇒ a faster)")
        lines.append("")
        lines.append("| a/b | ratio |")
        lines.append("|---|---|")
        for name, ratio in ab:
            cell = "n/a (pair side missing)" if ratio is None \
                else f"{ratio:.2f}"
            lines.append(f"| {_escape(name)} | {cell} |")
    return "\n".join(lines) + "\n"


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    print("## §Dry-run\n")
    print(dryrun_table())
    print("\n## §Roofline\n")
    print(roofline_table())
    print("\n## §Benchmarks\n")
    print(bench_table(argv if argv else [BASELINE]))


if __name__ == "__main__":
    main()
