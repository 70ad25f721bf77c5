"""The control of ``correct``: the plain reference put in the program's
place and computed one precision below what the configuration states
(fp8 for bfloat16 compute) must come out not correct. At smoke size on the
CPU, against the same limits the cells hold the program to."""
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

from bench import harness  # noqa: E402
from bench.reference.dataplane import TokenGenerator  # noqa: E402
from bench.tests.smoke import smoke_cell  # noqa: E402

SEED = 2**32 + 77


def test_fp8_control_is_not_correct_and_the_program_is():
    cell = smoke_cell("granite8b-pretrain", "steady")
    cfg, limits = cell.config, cell.config["limits"]
    run = harness.run_cell(cell, SEED, 0.0, False,
                           t_start=time.perf_counter(), limits=limits)
    assert all(v <= lim for v, lim in run.checks.values()), run.checks
    t = cfg["train"]
    gen = TokenGenerator(SEED, cfg["model"]["vocab_size"], t["global_batch"],
                         t["seq_len"], cell.traffic["zipf_s"])
    control = harness.reference(cfg).reference_steps(
        cfg["model"], cfg["optimizer"], SEED,
        [gen.grid(*i) for i in run.check_ids], matmul="fp8")
    got = harness.compare_steps(control, run.ref, limits)
    assert any(v > lim for v, lim in got.values()), got
