"""RunManifest + TrainSession: atomic model+data recovery (ISSUE 5 tentpole).

Covers the record/store layer (schema versioning, conditional-put sequence
claims), the TrainSession save/resume round trip, exactly-once recovery from
a kill between model upload and RunManifest commit, RunManifest-bounded
reclamation, and the fsck audits of the aligned chain.
"""
import time

import numpy as np
import pytest

from repro.core import (InjectedCrash, FaultInjector, MemoryObjectStore,
                        Namespace, Reclaimer, Watermark, read_trim_marker,
                        write_watermark)
from repro.dataplane import Checkpoint, Topology
from repro.obs.tracer import TRACER, disable_tracing, enable_tracing
from repro.ops import fsck
from repro.run import (RunManifest, RunManifestError, RunManifestStore,
                       TrainSession)

NS = "runs/test_run"


def _fill(session: TrainSession, n: int, nbytes: int = 256) -> None:
    with session.writer("P") as w:
        for _ in range(n):
            w.write(uniform_slice_bytes=nbytes)
        w.flush()


def _drain(readers, n):
    out = []
    for _ in range(n):
        batches = [r.next_batch(timeout_s=10) for r in readers]
        out.append(b"".join(b.payload for b in batches))
    return out


# ---------------------------------------------------------------------------
# RunManifest record + store
# ---------------------------------------------------------------------------

def test_runmanifest_roundtrip_and_schema_guard():
    ck = Checkpoint("tgb", version=3, step=7, topology=(2, 1), data_dp=2)
    rm = RunManifest(seq=2, step=7, model_key="k/MANIFEST.ckpt",
                     data_token=ck.encode(), topology=(2, 1), data_dp=2,
                     global_batch=8, seq_len=64)
    back = RunManifest.unpack(rm.pack())
    assert back == rm
    assert back.data_checkpoint() == ck
    assert back.aligned_data_step() == 7
    with pytest.raises(RunManifestError, match="schema"):
        import msgpack

        RunManifest.unpack(msgpack.packb({"schema": 99}))
    with pytest.raises(RunManifestError):
        RunManifest.unpack(b"garbage")


def test_runmanifest_store_sequences_are_claimed_once():
    store = MemoryObjectStore()
    runs = RunManifestStore(Namespace(store, NS))
    assert runs.latest() is None
    ck = Checkpoint("tgb", version=0, step=1, topology=(1, 1), data_dp=1)
    a = runs.append(step=1, model_key="m1", data_token=ck.encode(),
                    topology=(1, 1), data_dp=1)
    b = runs.append(step=2, model_key="m2", data_token=ck.encode(),
                    topology=(1, 1), data_dp=1)
    assert (a.seq, b.seq) == (0, 1)
    assert runs.latest().model_key == "m2"
    # a stale incarnation loses the conditional put for a taken sequence
    stale = RunManifest(seq=1, step=9, model_key="mX",
                        data_token=ck.encode(), topology=(1, 1), data_dp=1)
    assert not runs.commit(stale)
    assert runs.read(1).model_key == "m2"


def test_runmanifest_watermark_derivation():
    single = Checkpoint("tgb", version=5, step=6, topology=(2, 1), data_dp=2)
    rm = RunManifest(seq=0, step=6, model_key="m", data_token=single.encode(),
                     topology=(2, 1), data_dp=2)
    assert rm.watermark() == Watermark(version=5, step=6)
    # captured on a 2x-resized mesh: logical steps convert to tgb units
    grown = Checkpoint("tgb", version=5, step=3, topology=(4, 1), data_dp=2)
    rm2 = RunManifest(seq=1, step=3, model_key="m", data_token=grown.encode(),
                      topology=(4, 1), data_dp=2)
    assert rm2.watermark() == Watermark(version=5, step=6)
    comp = Checkpoint("tgb", version=-1, step=10, mix_pos=10,
                      topology=(1, 1), data_dp=1,
                      streams=(("a", 4, 7), ("b", 2, 3)))
    rm3 = RunManifest(seq=2, step=10, model_key="m", data_token=comp.encode(),
                      topology=(1, 1), data_dp=1)
    assert rm3.watermark("a") == Watermark(version=4, step=7)
    assert rm3.watermark("b") == Watermark(version=2, step=3)
    with pytest.raises(RunManifestError):
        rm3.watermark()  # composite needs a stream name


# ---------------------------------------------------------------------------
# TrainSession: aligned save / resume
# ---------------------------------------------------------------------------

def test_train_session_round_trip_exactly_once():
    store = MemoryObjectStore()
    topo = Topology(dp=2, cp=1)
    sess = TrainSession(store, topo, namespace=NS)
    _fill(sess, 10)
    readers = [sess.reader(dp_rank=d) for d in range(2)]
    _drain(readers, 4)
    entry = sess.checkpoint({"w": np.arange(5, dtype=np.float32)})
    assert (entry.seq, entry.step) == (0, 4)
    tail = _drain(readers, 6)

    resumed = TrainSession.resume(store, NS)
    assert resumed.resume_step == 4
    state = resumed.restore_model({"w": np.zeros(5, np.float32)})
    assert np.array_equal(np.asarray(state["w"]),
                          np.arange(5, dtype=np.float32))
    r2 = [resumed.reader(dp_rank=d) for d in range(2)]
    assert _drain(r2, 6) == tail  # byte-identical replay: exactly-once


class _SlowPuts(MemoryObjectStore):
    """A store whose PUTs take ``delay`` seconds (2 ms by default), so a
    save lasts long enough to time."""

    def __init__(self, delay: float = 0.002, **kw):
        super().__init__(**kw)
        self.delay = delay

    def put(self, key, data):
        time.sleep(self.delay)
        super().put(key, data)


@pytest.fixture
def pooled(monkeypatch):
    """A pool threshold of 16 bytes, so a few-leaf state takes the
    concurrent path."""
    from repro.train import checkpoint as ckpt

    monkeypatch.setattr(ckpt, "POOL_MIN_BYTES", 16)
    return ckpt


def _session_at_step(store, steps: int = 2) -> TrainSession:
    sess = TrainSession(store, Topology(dp=2, cp=1), namespace=NS)
    _fill(sess, 2 * steps + 4)
    readers = [sess.reader(dp_rank=d) for d in range(2)]
    _drain(readers, steps)
    return sess


def _traced(fn):
    enable_tracing()
    TRACER.clear()
    try:
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
    finally:
        disable_tracing()
    spans = TRACER.spans()
    TRACER.clear()
    return out, wall, spans


def test_checkpoint_spans_tile_the_save_and_count_its_bytes(pooled):
    store = _SlowPuts(delay=0.01)
    sess = _session_at_step(store)
    leaves = [np.arange(6, dtype=np.float32), np.ones((3, 4), np.int32),
              np.zeros(2, np.float64)]
    state = {"a": leaves[0], "b": {"c": leaves[1]}, "d": leaves[2]}
    sess.checkpoint(state)     # starts the pool's threads, outside the spans
    bytes0, puts0 = sess.stats.checkpoint_bytes, sess.stats.checkpoint_puts
    entry, wall, spans = _traced(lambda: sess.checkpoint(state))

    upload = [s for s in spans if s.name == "checkpoint.upload"]
    assert len(upload) == 1
    up = upload[0]
    to_host = [s for s in spans if s.name == "checkpoint.to_host"]
    drain = [s for s in spans if s.name == "checkpoint.drain"]
    puts = [s for s in spans if s.name == "checkpoint.put"]
    leaf_puts = [s for s in puts if s.args and "leaf" in s.args]
    manifest_put = [s for s in puts if s not in leaf_puts]
    assert len(to_host) == len(leaves) and len(drain) == 1
    assert len(leaf_puts) == len(leaves) and len(manifest_put) == 1
    # the leaves' PUTs ran on the pool, under the upload
    assert all(s.parent == up.id for s in to_host + drain + puts)
    assert all(s.tid != up.tid for s in leaf_puts)
    # largest leaf first: b/c (48 bytes), a (24), d (16)
    assert [s.args["leaf"] for s in to_host] == [1, 0, 2]
    manifest = store.get(entry.model_key)
    assert sess.stats.checkpoint_bytes - bytes0 == \
        sum(a.nbytes for a in leaves) + len(manifest)
    assert sess.stats.checkpoint_puts - puts0 == len(leaves) + 1
    top = [s for s in spans if s.parent is None and s.cat == "checkpoint"]
    assert [s.name for s in top] == ["checkpoint.claim", "checkpoint.upload",
                                     "checkpoint.commit",
                                     "checkpoint.watermarks"]
    assert all(s.args["step"] == 2 for s in top)
    assert sum(s.dur for s in top) == pytest.approx(wall, rel=0.10)
    # the trainer thread's copies, its wait and the MANIFEST tile the upload
    assert sum(s.dur for s in to_host + drain + manifest_put) == \
        pytest.approx(up.dur, rel=0.10)
    assert all(s.tid == up.tid for s in to_host + drain + manifest_put)


def test_small_checkpoint_stays_on_the_calling_thread():
    """Under the pool threshold the save runs one request at a time: each
    leaf's copy then its PUT, largest leaf first, on the trainer's
    thread."""
    sess = _session_at_step(_SlowPuts())
    state = {"a": np.arange(6, dtype=np.float32), "b": np.ones(9, np.int32)}
    _entry, _wall, spans = _traced(lambda: sess.checkpoint(state))
    up = [s for s in spans if s.name == "checkpoint.upload"][0]
    parts = [(s.name, (s.args or {}).get("leaf")) for s in spans
             if s.parent == up.id]
    assert parts == [("checkpoint.to_host", 1), ("checkpoint.put", 1),
                     ("checkpoint.to_host", 0), ("checkpoint.put", 0),
                     ("checkpoint.drain", None), ("checkpoint.put", None)]
    assert all(s.tid == up.tid for s in spans if s.parent == up.id)
    assert sess.stats.checkpoint_puts_inflight_peak == 1


def test_checkpoint_leaf_puts_overlap(pooled):
    store = _SlowPuts(delay=0.02)
    sess = _session_at_step(store)
    state = {f"w{i}": np.full(8, i, np.float32) for i in range(8)}
    _entry, _wall, spans = _traced(lambda: sess.checkpoint(state))
    up = [s for s in spans if s.name == "checkpoint.upload"][0]
    leaf_puts = [s for s in spans if s.name == "checkpoint.put"
                 and s.args and "leaf" in s.args]
    assert len(leaf_puts) == 8
    assert up.dur < 0.5 * sum(s.dur for s in leaf_puts)
    assert sess.stats.checkpoint_puts_inflight_peak > 1
    assert sess.stats.checkpoint_puts == 9
    resumed = TrainSession.resume(store, NS)
    back = resumed.restore_model({k: np.zeros(8, np.float32) for k in state})
    for k, v in state.items():
        assert np.asarray(back[k]).tobytes() == v.tobytes()


def test_save_holds_at_most_held_bytes_of_copies(pooled, monkeypatch):
    """Bytes of PUTs in flight count against the bound too: with room for
    two 32-byte leaves, no more than two PUTs run at once, and the trainer
    waits for them in ``checkpoint.drain`` spans."""
    monkeypatch.setattr(pooled, "HELD_BYTES", 64)
    store = _SlowPuts(delay=0.02)
    sess = _session_at_step(store)
    state = {f"w{i}": np.full(8, i, np.float32) for i in range(6)}
    _entry, _wall, spans = _traced(lambda: sess.checkpoint(state))
    assert sess.stats.checkpoint_puts_inflight_peak == 2
    up = [s for s in spans if s.name == "checkpoint.upload"][0]
    drain = [s for s in spans if s.name == "checkpoint.drain"]
    assert len(drain) > 1 and all(s.parent == up.id for s in drain)
    to_host = [s for s in spans if s.name == "checkpoint.to_host"]
    manifest_put = [s for s in spans if s.name == "checkpoint.put"
                    and not (s.args and "leaf" in s.args)]
    assert sum(s.dur for s in to_host + drain + manifest_put) == \
        pytest.approx(up.dur, rel=0.10)


def test_failed_leaf_put_on_the_pool_leaves_no_manifest(pooled):
    store = _SlowPuts(delay=0.01, faults=FaultInjector())
    sess = _session_at_step(store)
    sess.checkpoint({"w": np.arange(4, dtype=np.float32)})   # aligned @ 2
    readers = sess._readers
    _drain(readers, 1)
    store.faults.crash_on("put", "leaf-", nth=2)
    state = {f"w{i}": np.full(8, i, np.float32) for i in range(6)}
    with pytest.raises(InjectedCrash):
        sess.checkpoint(state)
    claimed = sess.ns.key("checkpoints", "0000000003")
    keys = store.list(claimed)
    time.sleep(0.05)
    assert store.list(claimed) == keys         # no PUT left in flight
    store.faults = None
    assert f"{claimed}/CLAIM" in keys
    assert not any(k.endswith("MANIFEST.ckpt") for k in keys)
    assert sess.runs.latest().step == 2        # no entry for the failed save
    assert sess.stats.checkpoints == 1

    resumed = TrainSession.resume(store, NS)
    assert resumed.resume_step == 2
    back = resumed.restore_model({"w": np.zeros(4, np.float32)})
    assert np.asarray(back["w"]).tobytes() == \
        np.arange(4, dtype=np.float32).tobytes()
    report = fsck(Namespace(store, NS))
    assert any(i.kind == "pending-model-checkpoint" and "0000000003" in i.key
               for i in report.issues), report.summary()


def test_checkpoint_counts_stay_exact_under_thread_churn(pooled,
                                                         monkeypatch):
    """More pool threads than cores, a tiny bound on held bytes and a short
    switch interval: a lost update would break the counts or the bytes."""
    import os
    import sys

    from repro.core import IOPool

    pool = IOPool(2 * (os.cpu_count() or 4), name="test-ckpt")
    monkeypatch.setattr(pooled, "_pool", pool)
    monkeypatch.setattr(pooled, "HELD_BYTES", 64)
    store = MemoryObjectStore()
    sess = _session_at_step(store)
    rng = np.random.default_rng(5)
    state = {f"w{i:03d}": rng.standard_normal(int(rng.integers(1, 40)))
             .astype(np.float32) for i in range(200)}
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        t0 = time.perf_counter()
        entry = sess.checkpoint(state)
        assert time.perf_counter() - t0 < 60
    finally:
        sys.setswitchinterval(old)
    manifest = store.get(entry.model_key)
    assert sess.stats.checkpoint_puts == len(state) + 1
    assert sess.stats.checkpoint_bytes == \
        sum(v.nbytes for v in state.values()) + len(manifest)
    assert 1 <= sess.stats.checkpoint_puts_inflight_peak <= pool.max_workers
    back = TrainSession.resume(store, NS).restore_model(
        {k: np.zeros_like(v) for k, v in state.items()})
    pool.shutdown()
    assert all(np.asarray(back[k]).tobytes() == v.tobytes()
               for k, v in state.items())


def test_restore_gets_run_under_the_restore_span(pooled):
    store = MemoryObjectStore()
    sess = _session_at_step(store)
    state = {"a": np.arange(40, dtype=np.float32), "b": np.ones(3, np.int8)}
    sess.checkpoint(state)
    resumed = TrainSession.resume(store, NS)
    template = {k: np.zeros_like(v) for k, v in state.items()}
    back, _wall, spans = _traced(lambda: resumed.restore_model(template))
    restore = [s for s in spans if s.name == "checkpoint.restore"]
    assert len(restore) == 1
    gets = [s for s in spans if s.name == "checkpoint.get"]
    # the MANIFEST on the calling thread, then one GET per leaf on the pool
    assert len(gets) == 1 + 2
    assert all(s.parent == restore[0].id for s in gets)
    leaf_gets = [s for s in gets if s.args and "leaf" in s.args]
    assert sorted(s.args["leaf"] for s in leaf_gets) == [0, 1]
    assert all(s.tid != restore[0].tid for s in leaf_gets)
    assert all(np.asarray(back[k]).tobytes() == v.tobytes()
               for k, v in state.items())


def test_train_session_checkpoint_requires_readers_and_lockstep():
    store = MemoryObjectStore()
    sess = TrainSession(store, Topology(dp=2, cp=1), namespace=NS)
    with pytest.raises(RuntimeError, match="readers"):
        sess.checkpoint({"w": np.zeros(1)})
    _fill(sess, 4)
    readers = [sess.reader(dp_rank=d) for d in range(2)]
    readers[0].next_batch(timeout_s=10)  # rank 0 runs ahead
    with pytest.raises(RuntimeError, match="lockstep"):
        sess.checkpoint({"w": np.zeros(1)})


def test_train_session_resume_without_entries_raises():
    with pytest.raises(KeyError, match="no RunManifest"):
        TrainSession.resume(MemoryObjectStore(), NS)


def test_train_session_rejects_non_tgb_backend():
    from repro.dataplane.types import UnsupportedOperation

    with pytest.raises(UnsupportedOperation, match="tgb"):
        TrainSession(MemoryObjectStore(), Topology(dp=1, cp=1), backend="mq")


def test_kill_between_upload_and_commit_resumes_aligned():
    store = MemoryObjectStore(faults=FaultInjector())
    sess = TrainSession(store, Topology(dp=1, cp=1), namespace=NS)
    _fill(sess, 8)
    r = sess.reader()
    seen = [r.next_batch(timeout_s=10).payload for _ in range(3)]
    sess.checkpoint({"w": np.float32(1.0)})
    lost = [r.next_batch(timeout_s=10).payload for _ in range(2)]
    store.faults.crash_on("cput", key_substr=".rm", nth=1)
    with pytest.raises(InjectedCrash):
        sess.checkpoint({"w": np.float32(2.0)})
    store.faults = None

    resumed = TrainSession.resume(store, NS)
    assert resumed.resume_step == 3
    state = resumed.restore_model({"w": np.float32(0.0)})
    assert float(np.asarray(state["w"])) == 1.0  # the ALIGNED model
    r2 = resumed.reader()
    replay = [r2.next_batch(timeout_s=10).payload for _ in range(5)]
    assert replay[:2] == lost
    assert seen + replay == seen + lost + replay[2:]


# ---------------------------------------------------------------------------
# Reclamation tied to the aligned checkpoint
# ---------------------------------------------------------------------------

def test_reclaimer_bounded_by_runmanifest_not_rank_files():
    store = MemoryObjectStore()
    topo = Topology(dp=1, cp=1)
    sess = TrainSession(store, topo, namespace=NS)
    _fill(sess, 10)
    r = sess.reader()
    for _ in range(4):
        r.next_batch(timeout_s=10)
    sess.checkpoint({"w": np.float32(0)})       # aligned @ step 4
    for _ in range(5):
        r.next_batch(timeout_s=10)
    # a stray per-rank watermark claims step 9 — the aligned entry must win
    write_watermark(sess.ns, 0, Watermark(version=r.checkpoint().version,
                                          step=9))
    sess.reclaim()
    trim = read_trim_marker(sess.ns)
    assert trim is not None and trim[0] == 4, \
        f"trim must stop at the aligned checkpoint, got {trim}"
    # and the aligned entry's batches are still replayable
    resumed = TrainSession.resume(store, NS)
    r2 = resumed.reader()
    assert len([r2.next_batch(timeout_s=10) for _ in range(6)]) == 6


# ---------------------------------------------------------------------------
# fsck: RunManifest <-> manifest <-> trim audits
# ---------------------------------------------------------------------------

def _aligned_run(store):
    sess = TrainSession(store, Topology(dp=1, cp=1), namespace=NS)
    _fill(sess, 6)
    r = sess.reader()
    for _ in range(3):
        r.next_batch(timeout_s=10)
    sess.checkpoint({"w": np.arange(3, dtype=np.float32)})
    return sess


def test_fsck_clean_on_aligned_run():
    store = MemoryObjectStore()
    _aligned_run(store)
    report = fsck(Namespace(store, NS))
    assert report.clean, report.summary()


def test_fsck_flags_torn_model_checkpoint():
    store = MemoryObjectStore()
    sess = _aligned_run(store)
    leaf = [k for k in store.list(sess.ns.key("checkpoints"))
            if "leaf-" in k][0]
    store.delete(leaf)
    report = fsck(Namespace(store, NS))
    assert any(i.kind == "torn-model-checkpoint" for i in report.issues)
    assert not report.clean


def test_fsck_flags_trim_past_aligned_cursor():
    import msgpack

    store = MemoryObjectStore()
    sess = _aligned_run(store)
    store.put(sess.ns.trim_key(),
              msgpack.packb({"safe_step": 99, "safe_version": -1}))
    report = fsck(Namespace(store, NS))
    assert any(i.kind == "trim-skew" for i in report.issues)


def test_fsck_orphan_model_upload_detected_and_repaired():
    from repro.train.checkpoint import upload_model_state

    store = MemoryObjectStore()
    sess = _aligned_run(store)                 # aligned @ step 3
    r = sess._readers[0]
    for _ in range(2):
        r.next_batch(timeout_s=10)
    # simulate the fatal window: upload @5 with no RunManifest commit...
    upload_model_state(sess.ns, 5, {"w": np.zeros(2, np.float32)})
    report = fsck(Namespace(store, NS))
    assert any(i.kind == "pending-model-checkpoint" for i in report.issues)
    # ...then a later aligned checkpoint supersedes it -> safe orphan
    r.next_batch(timeout_s=10)
    sess.checkpoint({"w": np.zeros(3, np.float32)})  # aligned @ step 6 > 5
    report = fsck(Namespace(store, NS))
    assert any(i.kind == "orphan-model-checkpoint" for i in report.issues)
    assert not report.clean
    fsck(Namespace(store, NS), repair=True)
    assert fsck(Namespace(store, NS)).clean


def test_fsck_flags_cursor_with_no_retained_manifests():
    """Catastrophic manifest loss must read as NOT CLEAN: the aligned
    entry's cursor names a version that no longer exists anywhere."""
    store = MemoryObjectStore()
    sess = _aligned_run(store)
    for key in store.list(sess.ns.key("manifest")):
        store.delete(key)
    report = fsck(Namespace(store, NS))
    assert any(i.kind == "runmanifest-unreadable-cursor"
               for i in report.issues), report.summary()
    assert not report.clean


def test_checkpoint_claims_directory_atomically():
    """A directory another incarnation already claimed (even with no
    MANIFEST yet — mid-upload) is never reused: the upload moves to the
    next retry-tagged directory instead of interleaving leaf objects."""
    store = MemoryObjectStore()
    sess = TrainSession(store, Topology(dp=1, cp=1), namespace=NS)
    _fill(sess, 4)
    r = sess.reader()
    for _ in range(2):
        r.next_batch(timeout_s=10)
    # another incarnation has claimed checkpoints/0000000002 mid-upload
    assert store.put_if_absent(
        sess.ns.key("checkpoints", "0000000002", "CLAIM"), b"claimed")
    entry = sess.checkpoint({"w": np.float32(7)})
    assert "0000000002-r1/" in entry.model_key
    resumed = TrainSession.resume(store, NS)
    state = resumed.restore_model({"w": np.float32(0)})
    assert float(np.asarray(state["w"])) == 7.0


def test_fsck_orphans_torn_upload_superseded_at_same_step():
    """The common cadence case: crash between upload and commit at step N,
    resume, replay, re-checkpoint at the SAME step N (lands in a retry-tagged
    dir). The torn untagged dir is superseded and must repair away."""
    store = MemoryObjectStore(faults=FaultInjector())
    sess = TrainSession(store, Topology(dp=1, cp=1), namespace=NS)
    _fill(sess, 8)
    r = sess.reader()
    for _ in range(2):
        r.next_batch(timeout_s=10)
    sess.checkpoint({"w": np.float32(1)})               # aligned @ 2
    for _ in range(2):
        r.next_batch(timeout_s=10)
    store.faults.crash_on("cput", key_substr=".rm", nth=1)
    with pytest.raises(InjectedCrash):
        sess.checkpoint({"w": np.float32(2)})           # torn upload @ 4
    store.faults = None

    resumed = TrainSession.resume(store, NS)
    r2 = resumed.reader()
    for _ in range(2):
        r2.next_batch(timeout_s=10)
    entry = resumed.checkpoint({"w": np.float32(3)})    # re-bind @ step 4
    assert "-r1/" in entry.model_key                    # torn dir untouched
    report = fsck(Namespace(store, NS))
    assert any(i.kind == "orphan-model-checkpoint" for i in report.issues)
    fsck(Namespace(store, NS), repair=True)
    assert fsck(Namespace(store, NS)).clean
    # the bound retry dir still restores
    again = TrainSession.resume(store, NS)
    assert float(np.asarray(again.restore_model({"w": np.float32(0)})["w"])) \
        == 3.0


def test_fsck_flags_corrupt_and_torn_runmanifest_chain():
    store = MemoryObjectStore()
    sess = _aligned_run(store)
    runs = sess.runs
    store.put(runs.key(2), b"not-msgpack")     # gap (seq 1) + corrupt entry
    report = fsck(Namespace(store, NS))
    kinds = {i.kind for i in report.issues}
    assert "torn-runmanifest-chain" in kinds
    assert "corrupt-runmanifest" in kinds


# ---------------------------------------------------------------------------
# Legacy token schema guard (satellite: versioned encode())
# ---------------------------------------------------------------------------

def test_v1_tokens_fail_with_clear_error():
    import base64

    import msgpack

    v1 = base64.urlsafe_b64encode(msgpack.packb(
        {"m": "bwck1", "b": "tgb", "v": 3, "s": 7})).decode("ascii")
    with pytest.raises(ValueError, match="retired.*re-checkpoint"):
        Checkpoint.decode(v1)
    # current tokens round-trip with the new fields
    ck = Checkpoint("tgb", version=3, step=7, topology=(2, 1), data_dp=2,
                    mix_pos=None)
    assert Checkpoint.decode(ck.encode()) == ck
