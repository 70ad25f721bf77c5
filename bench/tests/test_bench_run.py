"""The command's refusals, and each cell driven end to end at smoke size."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import jax  # noqa: E402
import pytest  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.tests.test_bench_faults import CELLS, run_main  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def fake_devices(monkeypatch, platform, kind, n=1):
    devs = [SimpleNamespace(platform=platform, device_kind=kind)] * n
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_refuses_the_cpu():
    with pytest.raises(SystemExit) as e:
        bench_run.check_device(1)
    assert "needs a TPU" in str(e.value.code)


def test_refuses_a_device_kind_missing_from_the_peak_table(monkeypatch):
    fake_devices(monkeypatch, "tpu", "TPU v99 imaginary")
    with pytest.raises(SystemExit) as e:
        bench_run.check_device(1)
    assert "no peaks" in str(e.value.code)


def test_refuses_fewer_chips_than_the_cell_asks_for(monkeypatch):
    fake_devices(monkeypatch, "tpu", "TPU v5 lite", n=1)
    with pytest.raises(SystemExit):
        bench_run.check_device(4)
    fake_devices(monkeypatch, "tpu", "TPU v5 lite", n=4)
    assert bench_run.check_device(4)["count"] == 4


def test_main_on_the_cpu_prints_no_result(monkeypatch, capsys):
    monkeypatch.setattr(bench_run, "configure_jax", lambda: None)
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "granite8b-pretrain.steady",
                        "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in spec["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
    for m in spec["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for m in spec["end_to_end"]:
        assert m["name"] in bench_run.END_TO_END


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_completes_and_is_correct(monkeypatch, capsys, cell):
    line, err = run_main(monkeypatch, capsys, cell)
    assert line["correct"] is True, line["checks"]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench_run.cell_metrics(spec, cell,
                                                      "end_to_end")}
    assert set(line["metrics"]) == want
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")
