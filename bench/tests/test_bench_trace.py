"""The reduction from a profiler trace to busy time, top operations, step
device time and labelled idle gaps: on plain events, and on a small trace
recorded on one TPU v5e (``data/v5e_small.xplane.pb``, made by
``make_trace_fixture.py``)."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import pytest  # noqa: E402

from bench.trace_reduce import reduce_events, reduce_trace  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"
MS = 1_000_000


def test_nested_operations_count_their_own_time():
    ann = [(0, 100 * MS, "bench.window")]
    ops = {"d": [(10 * MS, 90 * MS, "%while.1 = (s32[]) while(...)"),
                 (20 * MS, 30 * MS, "%fusion.7 = f32[8] fusion(...)"),
                 (40 * MS, 60 * MS, "%fusion.7 = f32[8] fusion(...)")]}
    r = reduce_events(ann, ops, {}, "x")
    assert dict(r["device_ops"]) == {"while.1": pytest.approx(0.05),
                                     "fusion.7": pytest.approx(0.03)}
    assert r["busy_s"] == pytest.approx(0.08)


def test_reduce_events_by_hand():
    ann = [(0, 100 * MS, "bench.window"), (0, 40 * MS, "bench.step"),
           (40 * MS, 100 * MS, "bench.checkpoint")]
    ops = {"/device:TPU:0": [(5 * MS, 20 * MS, "fusion.1"),
                             (15 * MS, 30 * MS, "fusion.2"),
                             (50 * MS, 60 * MS, "fusion.1"),
                             (90 * MS, 120 * MS, "copy")]}
    mods = {"/device:TPU:0": [(5 * MS, 30 * MS, "jit_train_step(1)"),
                              (50 * MS, 60 * MS, "jit_other(2)")]}
    r = reduce_events(ann, ops, mods, "train_step")
    assert r["window_s"] == pytest.approx(0.1)
    # busy: 5-30, 50-60, 90-100 (clipped to the window)
    assert r["busy_s"] == pytest.approx(0.045)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    assert r["step_device_s"] == pytest.approx(0.025)
    assert r["step_programs"] == 1
    gaps = {name: s for name, s in r["idle_gaps"]}
    # 60-90 lies inside the checkpoint annotation; 30-50 spans two
    assert gaps["bench.checkpoint"] == pytest.approx(0.03)
    assert gaps["bench.step..bench.checkpoint"] == pytest.approx(0.02)
    assert r["idle_gaps"][0][1] >= r["idle_gaps"][-1][1]


def test_reduce_events_needs_the_window():
    with pytest.raises(ValueError):
        reduce_events([(0, 1, "bench.step")], {"d": []}, {}, "x")


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    r = reduce_trace(str(FIXTURE), "lambda")
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # three ran; the device's clock runs about 1.2 ms behind the host's in
    # this trace, so the first falls just before the window opens
    assert r["step_programs"] >= 2
    # a module's span holds its operations and a few ns of launch
    assert r["step_device_s"] == pytest.approx(r["busy_s"], rel=0.01)
    assert r["device_ops"] and r["idle_gaps"]
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] \
        + 1e-9
