"""Roofline analysis over dry-run artifacts (assignment §ROOFLINE ANALYSIS).

Per (arch x shape x mesh) cell, from the loop-corrected HLO analysis:

    compute term    = HLO_FLOPs_per_device / 197e12          [bf16 peak/chip]
    memory term     = HLO_bytes_per_device / 819e9            [HBM BW/chip]
    collective term = collective_link_bytes_per_device / 4.5e10 [ICI BW/chip]

(The SPMD HLO is the per-device program, so HLO numbers are already per chip;
dividing by per-chip peaks is the assignment's formula with both sides divided
by `chips`.) MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE), D = tokens per
step (decode: global_batch, one new token each).

Usage:
    python -m repro.launch.roofline --dir experiments/dryrun [--csv out.csv]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

PEAK_FLOPS = 197e12       # bf16 / chip (v5e)
HBM_BW = 819e9            # B/s / chip
ICI_BW = 4.5e10           # usable B/s per link (~50 GB/s/link nominal)
HBM_PER_CHIP = 16 * 2**30


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    status: str
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    model_flops: float = 0.0
    hlo_flops_total: float = 0.0
    useful_ratio: float = 0.0
    step_time_s: float = 0.0
    mfu: float = 0.0
    peak_mem_gib: float = 0.0
    reason: str = ""


def tokens_per_step(rec: dict) -> int:
    if rec["kind"] == "decode":
        return rec["global_batch"]          # one new token per sequence
    return rec["global_batch"] * rec["seq_len"]


def model_flops(rec: dict) -> float:
    n = rec["active_params"] if rec["family"] == "moe" else rec["params"]
    d = tokens_per_step(rec)
    factor = 6.0 if rec["kind"] == "train" else 2.0  # fwd-only for serving
    return factor * n * d


def chips(rec: dict) -> int:
    return 512 if rec["mesh"] == "2x16x16" else 256


def analyze_record(rec: dict) -> RooflineRow:
    row = RooflineRow(arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
                      status=rec["status"], reason=rec.get("reason", ""))
    if rec["status"] != "ok":
        return row
    h = rec["hlo"]
    row.compute_s = h["flops"] / PEAK_FLOPS
    row.memory_s = h["bytes_accessed"] / HBM_BW
    row.collective_s = h["total_collective_bytes"] / ICI_BW
    terms = {"compute": row.compute_s, "memory": row.memory_s,
             "collective": row.collective_s}
    row.dominant = max(terms, key=terms.get)
    row.model_flops = model_flops(rec)
    row.hlo_flops_total = h["flops"] * chips(rec)
    row.useful_ratio = row.model_flops / max(1.0, row.hlo_flops_total)
    # roofline step time: max of the three overlapped terms (optimistic) —
    # we also report the sum-bound in the CSV consumer if needed.
    row.step_time_s = max(row.compute_s, row.memory_s, row.collective_s)
    ideal = row.model_flops / (chips(rec) * PEAK_FLOPS)
    row.mfu = ideal / row.step_time_s if row.step_time_s > 0 else 0.0
    row.peak_mem_gib = rec["memory"]["peak_per_device_bytes"] / 2**30
    return row


def load_rows(dirpath: str, tag: Optional[str] = None) -> List[RooflineRow]:
    rows = []
    for path in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        if tag and not path.endswith(f"__{tag}.json"):
            continue
        with open(path) as f:
            rec = json.load(f)
        rows.append(analyze_record(rec))
    return rows


def format_table(rows: List[RooflineRow]) -> str:
    hdr = (f"{'arch':22s} {'shape':12s} {'mesh':8s} {'status':8s} "
           f"{'compute_s':>10s} {'memory_s':>10s} {'collect_s':>10s} "
           f"{'dominant':>10s} {'useful':>7s} {'MFU':>6s} {'mem GiB':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if r.status != "ok":
            lines.append(f"{r.arch:22s} {r.shape:12s} {r.mesh:8s} {r.status:8s}"
                         f"  -- {r.reason[:70]}")
            continue
        lines.append(
            f"{r.arch:22s} {r.shape:12s} {r.mesh:8s} {r.status:8s} "
            f"{r.compute_s:10.4f} {r.memory_s:10.4f} {r.collective_s:10.4f} "
            f"{r.dominant:>10s} {r.useful_ratio:7.3f} {r.mfu:6.3f} "
            f"{r.peak_mem_gib:8.2f}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--tag", default=None)
    ap.add_argument("--csv", default=None)
    args = ap.parse_args()
    rows = load_rows(args.dir, tag=args.tag)
    print(format_table(rows))
    if args.csv:
        import csv
        with open(args.csv, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["arch", "shape", "mesh", "status", "compute_s",
                        "memory_s", "collective_s", "dominant", "model_flops",
                        "hlo_flops_total", "useful_ratio", "step_time_s",
                        "mfu", "peak_mem_gib", "reason"])
            for r in rows:
                w.writerow([r.arch, r.shape, r.mesh, r.status, r.compute_s,
                            r.memory_s, r.collective_s, r.dominant,
                            r.model_flops, r.hlo_flops_total, r.useful_ratio,
                            r.step_time_s, r.mfu, r.peak_mem_gib, r.reason])


if __name__ == "__main__":
    main()
