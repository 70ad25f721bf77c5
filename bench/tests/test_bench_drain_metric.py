"""The reader of ``checkpoint.drain_s`` on hand-made span lists: a save
whose leaf PUTs run on other threads behind the trainer's copies."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import pytest  # noqa: E402

from bench.tests.test_bench_span_metrics import (  # noqa: E402
    read, save, span, steps)


def pooled_save(t0, leaves, to_host=0.2, put=0.5, drain=0.3):
    """A save whose leaf PUTs run on other threads behind the copies: the
    trainer's copies, then its wait for the PUTs, then the MANIFEST."""
    out = [span("pipeline.align", t0, 0.01, cat="checkpoint", step=0)]
    t = t0 + 0.01
    for i in range(leaves):
        out.append(span("checkpoint.to_host", t, to_host, cat="checkpoint",
                        leaf=i))
        out.append(span("checkpoint.put", t + to_host, put,
                        cat="checkpoint", leaf=i))
        t += to_host
    out.append(span("checkpoint.drain", t, drain, cat="checkpoint"))
    t += drain
    out.append(span("checkpoint.put", t, 0.02, cat="checkpoint"))
    out.append(span("checkpoint.upload", t0 + 0.01, t + 0.02 - t0 - 0.01,
                    cat="checkpoint", step=0))
    return out, t + 0.02


def test_checkpoint_drain_per_save():
    one, t = pooled_save(10.0, leaves=3, drain=0.3)
    two, _ = pooled_save(t + 5.0, leaves=3, drain=0.1)
    spans = one + two
    assert read("checkpoint.drain_s", spans) == pytest.approx(0.2)
    # overlapping PUTs are summed as before: busy time, not the wait
    assert read("checkpoint.put_s", spans) == pytest.approx(1.52)
    assert read("checkpoint.to_host_s", spans) == pytest.approx(0.6)


def test_checkpoint_drain_is_none_without_a_save_or_a_drain():
    only_steps, _ = steps(0, 1, 0.0)
    assert read("checkpoint.drain_s", only_steps) is None
    # a program that PUTs each leaf before the next copy has no drain span
    serial, _ = save(10.0, leaves=3)
    assert read("checkpoint.drain_s", serial) is None
