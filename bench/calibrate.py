"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--seconds 0] [--out readings.jsonl]

For every seed of ``--seeds`` it runs the cell as ``run.py`` does, with the
window cut to ``--seconds`` (one step at 0), and prints the numbers that
were compared: these sound runs of the program give each limit its lower
reading. For every seed of ``--control-seeds`` it also puts into the
program's place

- the control, the reference computed in fp8 (``matmul="fp8"``), and
- the half-batch fault, the reference trained on half of each grid's rows,

and prints what each reads against the float32 reference. A step that
returns its state unchanged reads 1 on the gradient and the change by their
definition and needs no run. All seeds run in one process, so the step and
the reference compile once. One JSON object per reading, on standard output
and appended to ``--out``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run as bench_run  # noqa: E402


def emit(out, **row) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    bench_run.configure_jax()
    from bench import harness
    from bench.reference import dataplane as ref_data
    cell = harness.load_cell(bench_run.CHECKOUT, args.workload)
    device = bench_run.check_device(cell.chips)
    cfg, limits = cell.config, cell.config["limits"]
    ref_model = harness.reference(cfg)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        run = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=t, limits=limits)
        emit(args.out, seed=seed, kind="program", device=device,
             checks={k: v for k, (v, _) in run.checks.items()},
             prog=run.prog, ref=run.ref, seconds=time.perf_counter() - t)
        if seed not in controls:
            continue
        gen = ref_data.TokenGenerator(
            seed, cfg["model"]["vocab_size"], cfg["train"]["global_batch"],
            cfg["train"]["seq_len"], cell.traffic["zipf_s"])
        grids = [gen.grid(*i) for i in run.check_ids]
        half = slice(0, cfg["train"]["global_batch"] // 2)
        for kind, kw in (("control_fp8", {"matmul": "fp8"}),
                         ("fault_half_batch", {"rows": half})):
            t = time.perf_counter()
            other = ref_model.reference_steps(cfg["model"], cfg["optimizer"],
                                              seed, grids, **kw)
            got = harness.compare_steps(other, run.ref, limits)
            emit(args.out, seed=seed, kind=kind,
                 checks={k: v for k, (v, _) in got.items()}, prog=other,
                 seconds=time.perf_counter() - t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
