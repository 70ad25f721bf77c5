"""Integration tests for the fused train loop (train/pipeline.py).

Covers the tentpole's three claims:
  * exactly-once at the token level — kill the loop mid-run after an aligned
    checkpoint, resume via TrainSession, and the packed-batch byte stream and
    loss trajectory replay identically;
  * stall attribution is honest — the per-step spans sum to wall clock within
    tolerance, and a deliberately throttled store (FaultPolicy slow-GETs)
    shifts the split toward data-wait;
  * fused packing — PackingTokenSource emits the same grids the packer
    would, off the critical path.
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.configs.registry import get_smoke_config
from repro.core import (BatchTimeout, FaultPolicy, FaultyObjectStore,
                        MemoryObjectStore)
from repro.dataplane import Topology, open_dataplane
from repro.dataplane.types import Batch, UnsupportedOperation
from repro.models import init_params, param_specs
from repro.obs.tracer import disable_tracing, enable_tracing
from repro.run.session import TrainSession
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.pipeline import (FusedTrainLoop, PackingTokenSource,
                                  ReaderFanInSource)
from repro.train.step import StepConfig, make_train_step

TOPO = Topology(dp=2, cp=1, global_batch=4, seq_len=32)


@pytest.fixture(scope="module")
def tiny_step():
    """One jitted smoke-size train step shared by every test (one compile)."""
    cfg = get_smoke_config("granite_8b")
    step_fn = jax.jit(make_train_step(cfg, OptimizerConfig(), StepConfig()))
    params = init_params(param_specs(cfg), seed=0)
    opt = init_opt_state(params)
    return cfg, step_fn, params, opt


def _token_stream(n_batches: int, vocab: int) -> np.ndarray:
    n = n_batches * TOPO.global_batch * TOPO.seq_len
    return ((np.arange(n) * 7 + 3) % vocab).astype(np.int32)


def _produce(session, n_batches: int, vocab: int) -> None:
    with session.writer("w0") as w:
        w.write_tokens(_token_stream(n_batches, vocab))


def _fan_in(session, **reader_opts) -> ReaderFanInSource:
    readers = [session.reader(dp_rank=d, **reader_opts)
               for d in range(TOPO.dp)]
    return ReaderFanInSource(readers, TOPO)


# ---------------------------------------------------------------------------
# exactly-once kill-and-resume
# ---------------------------------------------------------------------------

def test_kill_and_resume_replays_identical_batches_and_losses(tiny_step):
    cfg, step_fn, params, opt = tiny_step
    ns = "runs/fused_resume"

    # golden: 10 uninterrupted steps
    store_a = MemoryObjectStore()
    sess_a = TrainSession(store_a, TOPO, namespace=ns)
    _produce(sess_a, 12, cfg.vocab_size)
    golden_batches, golden_losses = [], []
    with FusedTrainLoop(_fan_in(sess_a), step_fn, params, opt,
                        topology=TOPO, depth=2, timeout_s=30.0) as loop:
        rep = loop.run(10, on_batch=lambda s, t: golden_batches.append(
            t.tobytes()))
    golden_losses = rep.losses
    sess_a.close()

    # run B: 4 steps, aligned checkpoint, then die with the ring staged ahead
    store_b = MemoryObjectStore()
    sess_b = TrainSession(store_b, TOPO, namespace=ns)
    _produce(sess_b, 12, cfg.vocab_size)
    b_batches = []
    loop_b = FusedTrainLoop(_fan_in(sess_b), step_fn, params, opt,
                            topology=TOPO, depth=2, timeout_s=30.0)
    with loop_b:
        rep_b = loop_b.run(4, on_batch=lambda s, t: b_batches.append(
            t.tobytes()))
        entry = loop_b.aligned_checkpoint(
            sess_b, {"params": loop_b.params, "opt": loop_b.opt_state})
    assert entry.step == 4      # bound at the consumed frontier, not the ring
    sess_b.close()              # crash: staged-but-unconsumed batches lost

    # resume: same namespace, fresh process state
    sess_c = TrainSession.resume(store_b, ns)
    assert sess_c.resume_step == 4
    state = sess_c.restore_model({"params": params, "opt": opt})
    loop_c = FusedTrainLoop(_fan_in(sess_c), step_fn,
                            state["params"], state["opt"],
                            topology=TOPO, depth=2, timeout_s=30.0)
    with loop_c:
        rep_c = loop_c.run(6, on_batch=lambda s, t: b_batches.append(
            t.tobytes()))
    sess_c.close()

    # byte-identical packed batches across the kill: exactly-once at the
    # token level, not just the TGB level
    assert b_batches == golden_batches
    np.testing.assert_allclose(rep_b.losses + rep_c.losses, golden_losses,
                               rtol=1e-6)


def test_fused_loop_over_mixed_streams_aligns_composite_cursors(tiny_step):
    """MixedReader under the ring: align/rewind must round-trip the
    composite (per-stream <V, S> + mix position) cursor."""
    cfg, step_fn, params, opt = tiny_step
    ns = "runs/fused_mixed"
    streams = {"web": 0.5, "code": 0.5}

    def fresh(store):
        return TrainSession(store, TOPO, namespace=ns, streams=streams)

    store = MemoryObjectStore()
    sess = fresh(store)
    for name in streams:
        with sess.writer("w0", stream=name) as w:
            w.write_tokens(_token_stream(8, cfg.vocab_size))

    batches = []
    loop = FusedTrainLoop(_fan_in(sess), step_fn, params, opt,
                          topology=TOPO, depth=2, timeout_s=30.0)
    with loop:
        loop.run(3, on_batch=lambda s, t: batches.append(t.tobytes()))
        entry = loop.aligned_checkpoint(
            sess, {"params": loop.params, "opt": loop.opt_state})
        loop.run(3, on_batch=lambda s, t: batches.append(t.tobytes()))
    assert entry.step == 3
    sess.close()

    resumed = TrainSession.resume(store, ns)
    assert resumed.resume_step == 3
    state = resumed.restore_model({"params": params, "opt": opt})
    replay = []
    with FusedTrainLoop(_fan_in(resumed), step_fn, state["params"],
                        state["opt"], topology=TOPO, depth=2,
                        timeout_s=30.0) as loop2:
        loop2.run(3, on_batch=lambda s, t: replay.append(t.tobytes()))
    resumed.close()
    assert replay == batches[3:]   # the mixed stream replays byte-identically


def test_packing_source_cannot_align_a_staged_ring():
    src = PackingTokenSource(lambda t: None, TOPO)
    with pytest.raises(UnsupportedOperation):
        src.restore(())


# ---------------------------------------------------------------------------
# stall attribution
# ---------------------------------------------------------------------------

def test_stall_spans_sum_to_wall_clock(tiny_step):
    cfg, step_fn, params, opt = tiny_step
    store = MemoryObjectStore()
    sess = TrainSession(store, TOPO, namespace="runs/fused_spans")
    _produce(sess, 10, cfg.vocab_size)
    with FusedTrainLoop(_fan_in(sess), step_fn, params, opt,
                        topology=TOPO, depth=2, timeout_s=30.0) as loop:
        loop.run(1)                    # absorb jit compile outside the window
        tracer = enable_tracing()
        try:
            rep = loop.run(6)
        finally:
            disable_tracing()
    sess.close()

    # the three critical-path span families account for each step's wall
    # clock; only loop bookkeeping (metrics dict, callback dispatch) is
    # unattributed
    critical = {"pipeline.data_wait", "pipeline.h2d", "pipeline.compute"}
    span_total = sum(s.dur for s in tracer.spans() if s.name in critical)
    wall_total = rep.totals()["wall_s"]
    assert span_total == pytest.approx(wall_total, rel=0.15)
    # and the report's own split agrees with its wall clock
    t = rep.totals()
    attributed = t["data_wait_s"] + t["h2d_s"] + t["compute_s"] + t["other_s"]
    assert attributed == pytest.approx(wall_total, rel=1e-6)
    fr = rep.stall_fractions()
    assert sum(fr.values()) == pytest.approx(1.0, abs=1e-6)


def test_each_step_splits_into_dispatch_and_sync(tiny_step):
    cfg, step_fn, params, opt = tiny_step
    sess = TrainSession(MemoryObjectStore(), TOPO, namespace="runs/fused_split")
    _produce(sess, 10, cfg.vocab_size)
    tracer = enable_tracing()
    tracer.clear()
    try:
        with FusedTrainLoop(_fan_in(sess), step_fn, params, opt,
                            topology=TOPO, depth=2, timeout_s=30.0) as loop:
            loop.run(2)
            loop.aligned_checkpoint(sess, {"params": loop.params})
            loop.run(2)
    finally:
        disable_tracing()
    sess.close()
    spans = tracer.spans()
    tracer.clear()

    # one dispatch and one sync per step, both children of its compute
    computes = {s.args["step"]: s for s in spans
                if s.name == "pipeline.compute"}
    assert sorted(computes) == [0, 1, 2, 3]
    for name in ("pipeline.dispatch", "pipeline.sync"):
        parts = [s for s in spans if s.name == name]
        assert sorted(s.args["step"] for s in parts) == [0, 1, 2, 3]
        assert all(s.parent == computes[s.args["step"]].id for s in parts)
    # the staging thread's spans carry the step they stage for, counted
    # again from the consumed frontier after the alignment's rewind
    align = next(s for s in spans if s.name == "pipeline.align")
    staged = sorted((s.t0, s.args["step"]) for s in spans
                    if s.name == "pipeline.stage.h2d")
    before = [k for t, k in staged if t < align.t0]
    after = [k for t, k in staged if t > align.t0]
    assert before == list(range(len(before))) and len(before) >= 2
    assert after[:2] == [2, 3]


def test_an_expert_models_counts_ride_the_sync(tiny_step):
    """The one sync per step fetches the whole metrics dict: an expert
    model's row counts become args of ``pipeline.sync`` and loop stats;
    Granite's sync carries its step alone."""
    moe = get_smoke_config("deepseek_moe_16b")
    moe_step = jax.jit(make_train_step(moe, OptimizerConfig(), StepConfig(
        microbatches=2)))
    moe_params = init_params(param_specs(moe), seed=0)
    cases = [(tiny_step[0], tiny_step[1], tiny_step[2], tiny_step[3]),
             (moe, moe_step, moe_params, init_opt_state(moe_params))]
    got = []
    for cfg, step_fn, params, opt in cases:
        sess = TrainSession(MemoryObjectStore(), TOPO,
                            namespace=f"runs/counts_{cfg.family}")
        _produce(sess, 4, cfg.vocab_size)
        tracer = enable_tracing()
        tracer.clear()
        try:
            with FusedTrainLoop(_fan_in(sess), step_fn, params, opt,
                                topology=TOPO, depth=2,
                                timeout_s=30.0) as loop:
                loop.run(2)
        finally:
            disable_tracing()
        sess.close()
        got.append(([s.args for s in tracer.spans()
                     if s.name == "pipeline.sync"], loop.stats))
        tracer.clear()
    (dense_args, dense_stats), (moe_args, moe_stats) = got
    assert dense_args == [{"step": 0}, {"step": 1}]
    assert dense_stats.moe_rows_local == 0
    # 4 x 32 tokens a step, top 2 of 8 experts, all held, one expert layer
    assert [a["moe_rows_local"] for a in moe_args] == [256, 256]
    assert all(32 <= a["moe_rows_max"] <= 64 for a in moe_args)
    assert moe_stats.moe_rows_local == 512
    assert moe_stats.moe_rows_max == moe_args[-1]["moe_rows_max"]


def test_throttled_store_shifts_split_toward_data_wait(tiny_step):
    cfg, step_fn, params, opt = tiny_step

    def run_arm(store) -> float:
        sess = open_dataplane(store, TOPO, backend="tgb",
                              namespace="runs/fused_throttle")
        with sess.writer("w0") as w:
            w.write_tokens(_token_stream(10, cfg.vocab_size))
        src = ReaderFanInSource(
            [sess.reader(dp_rank=d, prefetch_depth=1) for d in range(2)],
            TOPO)
        with FusedTrainLoop(src, step_fn, params, opt, topology=TOPO,
                            depth=2, timeout_s=30.0) as loop:
            loop.run(1)                # compile + ring warm
            rep = loop.run(6)
        sess.close()
        return rep.data_wait_frac

    healthy = run_arm(MemoryObjectStore())
    # brownout-style throttle: every TGB GET eats a 30ms slow-path penalty
    throttled = run_arm(FaultyObjectStore(MemoryObjectStore(), FaultPolicy(
        seed=0, slow_get_rate=1.0, slow_get_s=0.03, key_filter="/tgb/")))

    assert throttled > healthy + 0.2, (healthy, throttled)
    assert throttled > 0.4, throttled


# ---------------------------------------------------------------------------
# fused packing source
# ---------------------------------------------------------------------------

def test_packing_token_source_matches_direct_packer():
    chunks = [np.arange(i * 50, i * 50 + 50, dtype=np.int32)
              for i in range(6)]
    feed = iter(chunks)

    def pull(timeout_s):
        return next(feed, None)

    src = PackingTokenSource(pull, TOPO, pad_token=0)
    grids = []
    while True:
        try:
            grids.append(src.next_tokens(timeout_s=1.0))
        except BatchTimeout:
            break
    total = sum(c.size for c in chunks)
    gb_tokens = TOPO.global_batch * TOPO.seq_len
    assert len(grids) == -(-total // gb_tokens)     # ceil: remainder flushed
    flat = np.concatenate([g.ravel() for g in grids])
    np.testing.assert_array_equal(flat[:total],
                                  np.concatenate(chunks))
    np.testing.assert_array_equal(flat[total:],
                                  np.zeros(flat.size - total, np.int32))
    # pad accounting survived the fused path
    assert src.last_batch.token_count == total - (len(grids) - 1) * gb_tokens


def test_packing_source_deadline_holds_when_pull_ignores_budget():
    """A pull that never yields data (and ignores its timeout argument) must
    not let next_tokens overrun timeout_s; empty chunks mean 'no data yet'."""
    src = PackingTokenSource(lambda t: np.empty(0, np.int32), TOPO)
    t0 = time.monotonic()
    with pytest.raises(BatchTimeout):
        src.next_tokens(timeout_s=0.3)
    assert time.monotonic() - t0 < 2.0


def test_packing_source_tolerates_pull_timeouts_and_counts_samples():
    """In-pull BatchTimeouts and empty chunks are 'no data yet' (no sample
    charged); (tokens, n) tuples attribute per-chunk sample counts."""
    half = TOPO.global_batch * TOPO.seq_len // 2
    events = [BatchTimeout("not yet"),
              (np.arange(half, dtype=np.int32), 3),
              np.empty(0, np.int32),
              (np.arange(half, dtype=np.int32), 2)]
    feed = iter(events)

    def pull(timeout_s):
        ev = next(feed)
        if isinstance(ev, BaseException):
            raise ev
        return ev

    src = PackingTokenSource(pull, TOPO)
    grid = src.next_tokens(timeout_s=5.0)
    assert grid.shape == (TOPO.global_batch, TOPO.seq_len)
    # 3 + 2 from the two real chunks; the empty chunk and the in-pull
    # timeout charged nothing (the old default charged 1 per chunk)
    assert src.last_batch.num_samples == 5


# ---------------------------------------------------------------------------
# fan-in transactionality (torn-grid regression)
# ---------------------------------------------------------------------------

class _ScriptedReader:
    """Minimal BatchReader: deterministic grids, scriptable timeouts."""

    def __init__(self, dp_rank: int, fail_calls=()):
        self.dp_rank, self.cp_rank = dp_rank, 0
        self.step = 0
        self.calls = 0
        self.timeouts_seen = []
        self.fail_calls = set(fail_calls)

    def grid(self, step: int) -> np.ndarray:
        base = step * 1000 + self.dp_rank * 100
        n = TOPO.global_batch // TOPO.dp * TOPO.seq_len
        return np.arange(base, base + n, dtype=np.int32).reshape(
            TOPO.global_batch // TOPO.dp, TOPO.seq_len)

    def next_batch(self, timeout_s=None) -> Batch:
        self.calls += 1
        self.timeouts_seen.append(timeout_s)
        if self.calls in self.fail_calls:
            raise BatchTimeout("scripted timeout")
        b = Batch(payload=b"", step=self.step, version=0,
                  dp_rank=self.dp_rank, cp_rank=0, array=self.grid(self.step))
        self.step += 1
        return b

    def checkpoint(self) -> int:
        return self.step

    def restore(self, ck: int) -> None:
        self.step = ck


def test_fan_in_rewinds_advanced_readers_on_partial_timeout():
    """If reader (1,0) times out after (0,0) already advanced, the fan-in
    must rewind (0,0) so the retry re-fetches the same global step —
    otherwise the retried grid would tear across steps."""
    r0, r1 = _ScriptedReader(0), _ScriptedReader(1, fail_calls={1})
    src = ReaderFanInSource([r0, r1], TOPO)
    with pytest.raises(BatchTimeout):
        src.next_tokens(timeout_s=0.1)
    assert r0.step == 0                      # rewound, not left at 1
    grid = src.next_tokens(timeout_s=1.0)    # retry: both rows from step 0
    np.testing.assert_array_equal(grid[:2], r0.grid(0))
    np.testing.assert_array_equal(grid[2:], r1.grid(0))


def test_fan_in_refuses_mixed_step_grids():
    r0, r1 = _ScriptedReader(0), _ScriptedReader(1)
    r0.step = 1                              # simulate diverged cursors
    src = ReaderFanInSource([r0, r1], TOPO)
    with pytest.raises(RuntimeError, match="mixed global steps"):
        src.next_tokens(timeout_s=1.0)
    assert (r0.step, r1.step) == (1, 0)      # entry snapshot restored


def test_fan_in_shares_one_timeout_budget():
    """timeout_s bounds the whole fan-in: a slow early reader eats into the
    budget the later readers see (not dp*cp independent allowances)."""

    class _Slow(_ScriptedReader):
        def next_batch(self, timeout_s=None):
            time.sleep(0.05)
            return super().next_batch(timeout_s)

    r0, r1 = _Slow(0), _ScriptedReader(1)
    src = ReaderFanInSource([r0, r1], TOPO)
    src.next_tokens(timeout_s=0.25)
    assert r1.timeouts_seen[0] <= 0.22


# ---------------------------------------------------------------------------
# ring lifecycle vs exactly-once
# ---------------------------------------------------------------------------

def _wait_for_staged(loop, deadline_s: float = 10.0) -> None:
    deadline = time.monotonic() + deadline_s
    while True:
        with loop._cond:
            if loop._ring:
                return
        assert time.monotonic() < deadline, "staging ring never filled"
        time.sleep(0.01)


def test_stop_rewinds_cursors_to_consumed_frontier(tiny_step):
    """stop() with staged-but-unconsumed entries must leave the source at
    the consumed frontier, so a checkpoint taken after stop() replays the
    dropped entries instead of skipping them."""
    cfg, step_fn, params, opt = tiny_step
    store = MemoryObjectStore()
    sess = TrainSession(store, TOPO, namespace="runs/fused_stop")
    _produce(sess, 10, cfg.vocab_size)
    src = _fan_in(sess)
    loop = FusedTrainLoop(src, step_fn, params, opt, topology=TOPO,
                          depth=2, timeout_s=30.0)
    with loop:
        loop.run(3)
        _wait_for_staged(loop)    # the ring is ahead of the trainer
    # context exit ran stop(): cursors back at the consumed frontier
    for ck in src.cursors():
        assert ck.step == 3
    entry = loop.aligned_checkpoint(
        sess, {"params": loop.params, "opt": loop.opt_state})
    assert entry.step == 3        # not 3 + staged
    sess.close()


def test_failed_alignment_does_not_wedge_the_loop(tiny_step):
    """aligned_checkpoint over a non-restorable source refuses — but must
    resume staging and keep the staged tokens, not park the loop forever."""
    cfg, step_fn, params, opt = tiny_step
    chunks = iter(np.array_split(_token_stream(8, cfg.vocab_size), 16))
    src = PackingTokenSource(lambda t: next(chunks, None), TOPO)
    loop = FusedTrainLoop(src, step_fn, params, opt, topology=TOPO,
                          depth=2, timeout_s=30.0)
    with loop:
        loop.run(1)
        _wait_for_staged(loop)
        with pytest.raises(UnsupportedOperation):
            loop.aligned_checkpoint(object(), {})
        assert loop._pause is False          # staging resumed
        with loop._cond:
            assert loop._ring                # staged tokens not lost
        assert loop.run(2).steps == 2        # loop keeps training
