#!/usr/bin/env python
"""Render the fused-conv verdict from captured TPU measurements.

Reads the e2e sweep rows in `benchmarks/results.jsonl` (non-smoke,
accelerator-backend) and prints:

  1. a per-(batch, window) e2e table: unfused vs each fused variant,
  2. the verdict line — which variant (if any) beats unfused at the
     headline operating point, with the margin.

Pure file parsing (no device); run any time:
    python tools/fused_verdict.py
    python tools/fused_verdict.py --model resnet50
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
RESULTS = ROOT / "benchmarks" / "results.jsonl"


def load_results(metric_substr: str):
    rows = []
    try:
        lines = RESULTS.read_text().splitlines()
    except OSError:
        return rows
    for line in lines:
        try:
            r = json.loads(line)
        except json.JSONDecodeError:
            continue
        if (r.get("value") and not r.get("smoke")
                and r.get("backend") not in (None, "cpu")
                and metric_substr in r.get("metric", "")):
            rows.append(r)
    return rows


def variant_key(cfg: dict) -> str:
    fs = cfg.get("fused_stages") or ""
    if not fs:
        return "unfused"
    return f"fused[{fs}]" + ("+bwd" if cfg.get("fused_bwd") else "")


def e2e_table(rows):
    # newest row wins per (batch, window, variant)
    cells: dict = {}
    for r in sorted(rows, key=lambda r: r.get("ts", "")):
        cfg = r.get("config") or {}
        if cfg.get("xent") == "pallas":
            continue  # fused sweeps run jnp xent; keep cells like-for-like
        key = (cfg.get("per_chip_batch"), cfg.get("steps_per_call"),
               variant_key(cfg))
        cells[key] = r
    variants = sorted({k[2] for k in cells}, key=lambda v: (v != "unfused", v))
    points = sorted({(k[0], k[1]) for k in cells},
                    key=lambda p: (p[0] or 0, p[1] or 0))
    if not points:
        return None, variants, cells
    head = "| batch/chip | window | " + " | ".join(variants) + " |"
    sep = "|---" * (len(variants) + 2) + "|"
    lines = [head, sep]
    for b, w in points:
        row = [f"| {b} | {w} "]
        base = cells.get((b, w, "unfused"))
        for v in variants:
            r = cells.get((b, w, v))
            if r is None:
                row.append("| — ")
                continue
            val = f"{r['value']:,.0f}"
            if r.get("mfu") is not None:
                val += f" (.{round(r['mfu'] * 1000):03d})"
            if base and v != "unfused":
                val += f" {100 * (r['value'] / base['value'] - 1):+.1f}%"
            row.append(f"| {val} ")
        lines.append("".join(row) + "|")
    return "\n".join(lines), variants, cells


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--headline-batch", type=int, default=2048)
    ap.add_argument("--headline-window", type=int, default=30)
    args = ap.parse_args()

    rows = load_results(args.model)
    table, variants, cells = e2e_table(rows)
    print(f"# Fused-conv verdict ({args.model})\n")
    if table is None:
        print("No accelerator e2e rows yet — run `python bench.py "
              "--sweep-fused` on the chip.")
    else:
        print("## End-to-end (images/sec/chip, (MFU), % vs unfused)\n")
        print(table)

    # The verdict line.
    hb, hw = args.headline_batch, args.headline_window
    base = cells.get((hb, hw, "unfused")) if cells else None
    fused = [(v, cells[(hb, hw, v)]) for v in variants
             if v != "unfused" and (hb, hw, v) in cells] if cells else []
    print()
    if base and fused:
        best_v, best = max(fused, key=lambda kv: kv[1]["value"])
        margin = 100 * (best["value"] / base["value"] - 1)
        if margin > 0:
            print(f"VERDICT: {best_v} BEATS unfused at the headline point "
                  f"(b{hb}/w{hw}): {best['value']:,.0f} vs "
                  f"{base['value']:,.0f} img/s/chip ({margin:+.1f}%) — make "
                  f"it the headline config.")
        else:
            print(f"VERDICT: no fused variant beats unfused at the headline "
                  f"point (b{hb}/w{hw}); best is {best_v} at {margin:+.1f}% "
                  f"({best['value']:,.0f} vs {base['value']:,.0f}) — keep "
                  f"fused_stages default off, document as the Pallas "
                  f"exemplar.")
    else:
        print("VERDICT: pending — headline-point measurements for both "
              "unfused and fused variants not yet captured.")


if __name__ == "__main__":
    main()
