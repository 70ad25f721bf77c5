"""CI gate for fig17: fail if the fused train loop stops being compute-bound.

Usage: python benchmarks/check_fig17.py bench-smoke.csv

Checks (from the fig17 acceptance criteria):
  * tgb data-wait fraction stays under 15% at every staging depth >= 2;
  * tgb tokens/s at depth >= 2 is within 10% of the colocated baseline
    (best arm vs best arm at depth >= 2 — single-depth pairings are CPU
    scheduling noise at these step sizes);
  * the staging ring actually earns its keep: tgb depth 2 clearly beats the
    synchronous depth-0 arm, and depth 0 shows the stall the ring hides.
"""
from __future__ import annotations

import re
import sys
from typing import Dict

DEPTHS = (0, 2, 4)


def parse(path: str) -> Dict[str, Dict[str, float]]:
    rows: Dict[str, Dict[str, float]] = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("fig17/"):
                continue
            name, _us, derived = line.split(",", 2)
            fields = {}
            for kv in derived.split(";"):
                if "=" not in kv:
                    continue
                k, v = kv.split("=", 1)
                m = re.match(r"-?\d+(\.\d+)?", v)
                if m:
                    fields[k] = float(m.group(0))
            rows[name] = fields
    return rows


def main() -> int:
    path = sys.argv[1] if len(sys.argv) > 1 else "bench-smoke.csv"
    rows = parse(path)
    if not rows:
        print(f"check_fig17: no fig17 rows found in {path}", file=sys.stderr)
        return 2
    failures = []

    def arm(backend: str, depth: int) -> Dict[str, float]:
        return rows.get(f"fig17/{backend}/d{depth}", {})

    # data-wait fraction under threshold at every overlapped depth
    for d in (2, 4):
        frac = arm("tgb", d).get("data_wait_frac", 1.0)
        if frac >= 0.15:
            failures.append(f"tgb d{d} data_wait_frac {frac:.3f} >= 0.15 "
                            f"(loop is no longer compute-bound)")

    # tokens/s parity with the colocated baseline at depth >= 2
    tgb_best = max(arm("tgb", d).get("tokens_per_s", 0.0) for d in (2, 4))
    coloc_best = max(arm("colocated", d).get("tokens_per_s", 0.0)
                     for d in (2, 4))
    if coloc_best <= 0:
        failures.append("colocated baseline delivered nothing")
    elif tgb_best < 0.9 * coloc_best:
        failures.append(
            f"tgb best-at-depth>=2 {tgb_best:.0f} tokens/s < 90% of "
            f"colocated {coloc_best:.0f} tokens/s")

    # the ring earns its keep vs the synchronous strawman
    tgb_d0 = arm("tgb", 0)
    tgb_d2 = arm("tgb", 2)
    if tgb_d2.get("tokens_per_s", 0.0) < 1.15 * tgb_d0.get("tokens_per_s",
                                                           float("inf")):
        failures.append(
            f"tgb d2 {tgb_d2.get('tokens_per_s', 0):.0f} tokens/s not >= "
            f"1.15x the synchronous d0 arm "
            f"{tgb_d0.get('tokens_per_s', 0):.0f} (overlap inert)")
    if tgb_d0.get("data_wait_frac", 0.0) < \
            tgb_d2.get("data_wait_frac", 0.0) + 0.1:
        failures.append(
            f"tgb d0 data_wait_frac {tgb_d0.get('data_wait_frac', 0):.3f} "
            f"does not exceed d2's "
            f"{tgb_d2.get('data_wait_frac', 0):.3f} by 0.1 "
            f"(attribution no longer sees the stall the ring hides)")

    if failures:
        print("check_fig17: fused train loop regressed:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"check_fig17: OK ({len(rows)} fig17 rows, tgb best "
          f"{tgb_best:.0f} vs colocated {coloc_best:.0f} tokens/s, "
          f"tgb d2 data-wait {tgb_d2.get('data_wait_frac', 0):.1%}, "
          f"d0 strawman {tgb_d0.get('data_wait_frac', 0):.1%})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
