"""A whole run of each cell at smoke size, past the look for a chip, with
the timed path broken underneath: ``correct`` must come out false, once for
each fault a training cell can have. (One chip: no exchange between chips
to leave out.)"""
import functools
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.tests.faults import FAULTS  # noqa: E402
from bench.tests.smoke import smoke_cell  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
#: every cell of ``BENCHMARK.json``: name -> (config, traffic)
CELLS = {w["name"]: (w["config"], w["traffic"]) for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]}


def run_main(monkeypatch, capsys, cell_name, hooks=None, trace=0):
    """``run.main`` on the CPU at smoke size; returns the result line."""
    cell = smoke_cell(*CELLS[cell_name])
    monkeypatch.setattr(bench_run, "configure_jax", lambda: None)
    monkeypatch.setattr(bench_run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(harness, "load_cell", lambda root, name: cell)
    if hooks is not None:
        monkeypatch.setattr(harness, "run_cell",
                            functools.partial(harness.run_cell, hooks=hooks))
    assert bench_run.main(["--workload", cell_name, "--seed", str(2**33 + 1),
                           "--seconds", "0.3", "--trace", str(trace)]) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(monkeypatch, capsys, cell, fault):
    line, err = run_main(monkeypatch, capsys, cell, FAULTS[fault]())
    assert line["correct"] is False, line["checks"]
    assert "FAILED" in err.strip().splitlines()[-1] or any(
        "FAILED" in l for l in err.strip().splitlines()[-8:])
