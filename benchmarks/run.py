"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--full`` widens sweeps (closer to
paper scale); default is the quick profile (a few minutes on CPU).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

FIGS = [
    ("fig1", "benchmarks.fig1_expansion"),
    ("fig5", "benchmarks.fig5_end_to_end"),
    ("fig6", "benchmarks.fig6_producer_scaling"),
    ("fig7", "benchmarks.fig7_dac_ablation"),
    ("fig8", "benchmarks.fig8_exactly_once"),
    ("fig9", "benchmarks.fig9_lifecycle"),
    ("fig10", "benchmarks.fig10_consumer"),
    ("fig11", "benchmarks.fig11_multisource"),
    ("fig12", "benchmarks.fig12_io_path"),
    ("fig13", "benchmarks.fig13_failure_isolation"),
    ("fig14", "benchmarks.fig14_aligned_recovery"),
    ("fig15", "benchmarks.fig15_derived_streams"),
    ("fig16", "benchmarks.fig16_brownout"),
    ("fig17", "benchmarks.fig17_fused_train"),
    ("fig18", "benchmarks.fig18_sharded_commit"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated figure ids (fig5,fig7,...)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    selected = set(args.only.split(",")) if args.only else None

    import importlib

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for fid, module_name in FIGS:
        if selected and fid not in selected:
            continue
        t0 = time.monotonic()
        try:
            mod = importlib.import_module(module_name)
            rows = mod.run(quick=not args.full)
            for row in rows:
                print(row.csv(), flush=True)
        except Exception as e:
            failures += 1
            print(f"{fid}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {fid} done in {time.monotonic() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == '__main__':
    main()
