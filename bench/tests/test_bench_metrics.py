"""Each per-layer reader on a recorded run by hand, and on a run that gives
it nothing to read (it must return None, never 0)."""
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import json  # noqa: E402

import pytest  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.harness import Run  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAMES = [m["name"] for m in SPEC["per_layer"]]


def recorded() -> Run:
    cfg = json.loads((ROOT / "bench/configs/granite8b-ckpt16.json").read_text())
    step = SimpleNamespace(data_wait_s=0.01)
    return Run(
        window_s=40.0, window_tokens=16 * 16384, config=cfg,
        device_kind="TPU v5 lite",
        reports=[SimpleNamespace(timings=[step] * 16)],
        stalls_s=[24.0, 26.0], restore_s=13.5, staged_batches=20,
        spans=[SimpleNamespace(name="pipeline.stage.fetch", dur=0.002)] * 10
        + [SimpleNamespace(name="pipeline.data_wait", dur=1.0)],
        trace={"window_s": 40.0, "busy_s": 13.0, "devices": 1,
               "step_device_s": 12.8, "step_programs": 16})


def test_readers_on_a_recorded_run():
    r = recorded()
    got = {n: bench_run.read_metric(n, r) for n in NAMES}
    assert got["mfu"] == pytest.approx(
        100 * 51951924412416.0 * 16 / 40.0 / 197e12)
    assert got["step.device_ms"] == pytest.approx(800.0)
    assert got["device.idle_share"] == pytest.approx(67.5)
    assert got["pipeline.data_wait_share"] == pytest.approx(0.4)
    assert got["read.fetch_ms"] == pytest.approx(1.0)
    assert got["checkpoint.stall_s"] == pytest.approx(25.0)
    assert got["checkpoint.restore_s"] == pytest.approx(13.5)


@pytest.mark.parametrize("name", NAMES)
def test_reader_with_nothing_to_read_returns_none(name):
    assert bench_run.read_metric(name, Run()) is None
