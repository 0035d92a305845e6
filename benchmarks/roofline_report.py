"""Assemble roofline reports: dry-run sweep tables + solver cost tables.

Two sources, one renderer:

  * ``results/dryrun/*.json`` (``python -m repro.launch.dryrun --out``) -- the
    markdown tables for EXPERIMENTS.md (``--table roofline`` /
    ``--table dryrun``).  The results directory is a flag now
    (``--results-dir``), not a hard-coded path, so sweeps written
    anywhere (CI artifacts, scratch dirs) render the same.
  * a ``BENCH_batched.json`` run document (``--bench``) -- the per-stage
    solver cost table: HLO-derived flops / HBM bytes / arithmetic
    intensity, the roofline-predicted seconds, and the achieved
    roofline fraction for rows that carry measured wall time (the
    ``cost`` records attached by benchmarks/bench_batched.py).

``--out FILE`` writes the rendered markdown instead of printing it.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DEFAULT_RESULTS = REPO / "results" / "dryrun"


def load(results_dir: Path, mesh: str):
    rows = []
    for f in sorted(Path(results_dir).glob(f"*__{mesh}.json")):
        rows.append(json.loads(f.read_text()))
    return rows


def fmt_s(x):
    if x == 0:
        return "0"
    if x < 1e-3:
        return f"{x*1e6:.0f}us"
    if x < 1:
        return f"{x*1e3:.1f}ms"
    return f"{x:.2f}s"


def fmt_b(x):
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if x >= div:
            return f"{x/div:.1f}{unit}"
    return f"{x:.0f}B"


def roofline_table(rows):
    hdr = (
        "| arch | shape | kind | flops/dev | HBM B/dev | coll B/dev | "
        "compute | memory | collective | bound | useful | mem/dev |\n"
        "|---|---|---|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in rows:
        if "roofline" not in r:
            continue
        rf = r["roofline"]
        mem = r.get("memory", {}).get("total_per_device", 0)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r.get('kind','?')} | "
            f"{rf['flops']:.2e} | {fmt_b(rf['bytes_accessed'])} | "
            f"{fmt_b(rf['coll_bytes'])} | {fmt_s(rf['compute_s'])} | "
            f"{fmt_s(rf['memory_s'])} | {fmt_s(rf['collective_s'])} | "
            f"**{rf['bottleneck']}** | {rf['useful_ratio']:.2f} | "
            f"{fmt_b(mem)} |"
        )
    return hdr + "\n".join(lines)


def dryrun_table(rows):
    hdr = (
        "| arch | shape | mesh | chips | compile | params | mem/dev | "
        "all-reduce | all-gather | reduce-scatter | all-to-all | permute |\n"
        "|---|---|---|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for r in rows:
        if "roofline" not in r:
            continue
        cd = r["roofline"]["coll_detail"]

        def g(op):
            e = cd.get(op)
            return fmt_b(e["bytes"]) if e else "-"

        mem = r.get("memory", {}).get("total_per_device", 0)
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['chips']} | "
            f"{r['compile_s']}s | {r.get('params', r.get('n','-'))} | {fmt_b(mem)} | "
            f"{g('all-reduce')} | {g('all-gather')} | {g('reduce-scatter')} | "
            f"{g('all-to-all')} | {g('collective-permute')} |"
        )
    return hdr + "\n".join(lines)


def cost_table(doc: dict) -> str:
    """Per-stage solver cost table from a BENCH_batched.json document."""
    hw = "?"
    hdr = (
        "| row | stage | flops | HBM bytes | intensity | roofline | "
        "measured | achieved | bound |\n"
        "|---|---|---|---|---|---|---|---|---|\n"
    )
    lines = []
    for row in doc.get("rows", []):
        for stage, c in (row.get("cost") or {}).items():
            hw = c.get("hw", hw)
            intensity = c.get("intensity")
            measured = c.get("measured_s")
            frac = c.get("roofline_frac")
            lines.append(
                f"| {row['name']} | {stage} | {c['flops']:.3g} | "
                f"{fmt_b(c['hbm_bytes'])} | "
                + (f"{intensity:.2f}" if intensity is not None else "-")
                + f" | {fmt_s(c['roofline_s'])} | "
                + (fmt_s(measured) if measured else "-")
                + " | "
                + (f"{frac:.1%}" if frac is not None else "-")
                + f" | {c.get('bottleneck', '-')} |"
            )
    if not lines:
        return ("no `cost` records in this run document -- rerun "
                "benchmarks/bench_batched.py from a build with the cost "
                "observatory (repro.obs.cost)\n")
    title = (f"Per-stage solver cost ({doc.get('bench', '?')}, "
             f"hardware model `{hw}`, achieved = roofline_s / measured_s)\n\n")
    return title + hdr + "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--table", default="roofline",
                    choices=["roofline", "dryrun"])
    ap.add_argument("--results-dir", default=str(DEFAULT_RESULTS),
                    help="dry-run sweep results directory "
                         "(default: <repo>/results/dryrun)")
    ap.add_argument("--bench", default=None,
                    help="render the per-stage cost table from this "
                         "BENCH_batched.json instead of the sweep tables")
    ap.add_argument("--out", default=None,
                    help="write the rendered markdown here instead of stdout")
    args = ap.parse_args(argv)
    if args.bench:
        text = cost_table(json.loads(Path(args.bench).read_text()))
    else:
        results_dir = Path(args.results_dir)
        if not results_dir.exists():
            text = (f"no results under {results_dir} -- run "
                    "python -m repro.launch.dryrun --out first (or pass "
                    "--results-dir)\n")
        else:
            rows = load(results_dir, args.mesh)
            table = roofline_table if args.table == "roofline" else dryrun_table
            text = table(rows)
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(text if text.endswith("\n") else text + "\n")
        print(f"wrote {out}")
    else:
        print(text)


if __name__ == "__main__":
    main()
