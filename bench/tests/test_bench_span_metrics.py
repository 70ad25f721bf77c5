"""The readers of the program's spans on hand-made span lists: the host gap
between steps, and the parts of each checkpoint."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import pytest  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.harness import Run  # noqa: E402
from repro.obs.tracer import Span  # noqa: E402


def span(name, t0, dur, cat="compute", **args):
    return Span(name, cat, t0, dur, 0, args or None)


def steps(first, count, t0, period=1.0, dispatch=0.01, sync=0.9,
          host=0.002):
    """Each step: dispatch, then sync, then ``host`` seconds of loop."""
    out, t = [], t0
    for i in range(first, first + count):
        out.append(span("pipeline.compute", t, dispatch + sync, step=i))
        out.append(span("pipeline.dispatch", t, dispatch, step=i))
        out.append(span("pipeline.sync", t + dispatch, sync, step=i))
        t += dispatch + sync + host
    return out, t


def save(t0, leaves, to_host=0.2, put=0.5):
    out = [span("pipeline.align", t0, 0.01, cat="checkpoint", step=0)]
    t = t0 + 0.01
    for i in range(leaves):
        out.append(span("checkpoint.to_host", t, to_host, cat="checkpoint",
                        leaf=i))
        out.append(span("checkpoint.put", t + to_host, put,
                        cat="checkpoint", leaf=i))
        t += to_host + put
    out.append(span("checkpoint.put", t, 0.02, cat="checkpoint"))
    out.append(span("checkpoint.upload", t0 + 0.01, t + 0.02 - t0 - 0.01,
                    cat="checkpoint", step=0))
    return out, t + 0.02


def read(name, spans):
    return bench_run.read_metric(name, Run(spans=spans))


def test_host_gap_is_the_median_between_sync_and_next_dispatch():
    spans, _ = steps(0, 5, 100.0, host=0.002)
    # from the end of a sync to the end of the next dispatch: loop + enqueue
    assert read("step.host_gap_ms", spans) == pytest.approx(12.0)


def test_host_gap_skips_the_pair_with_a_checkpoint_between():
    a, t = steps(0, 2, 100.0, host=0.003)
    ck, t = save(t, leaves=2)
    b, _ = steps(2, 1, t, host=0.003)
    # the pair (1, 2) spans the save: counted, the median would be ~0.7 s
    assert read("step.host_gap_ms", a + ck + b) == pytest.approx(13.0)


def test_checkpoint_parts_per_save():
    one, t = save(10.0, leaves=3, to_host=0.2, put=0.5)
    two, _ = save(t + 5.0, leaves=3, to_host=0.4, put=0.7)
    spans = one + two
    assert read("checkpoint.to_host_s", spans) == pytest.approx(0.9)
    # three leaf PUTs and the MANIFEST's, per save
    assert read("checkpoint.put_s", spans) == pytest.approx(1.82)


def test_span_readers_find_nothing_without_their_spans():
    only_steps, _ = steps(0, 1, 0.0)
    for name in ("step.host_gap_ms", "checkpoint.to_host_s",
                 "checkpoint.put_s"):
        assert read(name, only_steps) is None
