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
    """A store whose PUTs take 2 ms, so a save lasts long enough to time."""

    def put(self, key, data):
        time.sleep(0.002)
        super().put(key, data)


def test_checkpoint_spans_tile_the_save_and_count_its_bytes():
    store = _SlowPuts()
    sess = TrainSession(store, Topology(dp=2, cp=1), namespace=NS)
    _fill(sess, 4)
    readers = [sess.reader(dp_rank=d) for d in range(2)]
    _drain(readers, 2)
    leaves = [np.arange(6, dtype=np.float32), np.ones((3, 4), np.int32),
              np.zeros(2, np.float64)]
    state = {"a": leaves[0], "b": {"c": leaves[1]}, "d": leaves[2]}
    enable_tracing()
    TRACER.clear()
    try:
        t0 = time.perf_counter()
        entry = sess.checkpoint(state)
        wall = time.perf_counter() - t0
    finally:
        disable_tracing()
    spans = TRACER.spans()
    TRACER.clear()

    upload = [s for s in spans if s.name == "checkpoint.upload"]
    assert len(upload) == 1
    to_host = [s for s in spans if s.name == "checkpoint.to_host"]
    puts = [s for s in spans if s.name == "checkpoint.put"]
    assert len(to_host) == len(leaves)
    assert len(puts) == len(leaves) + 1          # the leaves, then MANIFEST
    assert all(s.parent == upload[0].id for s in to_host + puts)
    manifest = store.get(entry.model_key)
    assert sess.stats.checkpoint_bytes == \
        sum(a.nbytes for a in leaves) + len(manifest)
    assert sess.stats.checkpoint_puts == len(leaves) + 1
    top = [s for s in spans if s.parent is None and s.cat == "checkpoint"]
    assert [s.name for s in top] == ["checkpoint.claim", "checkpoint.upload",
                                     "checkpoint.commit",
                                     "checkpoint.watermarks"]
    assert all(s.args["step"] == 2 for s in top)
    assert sum(s.dur for s in top) == pytest.approx(wall, rel=0.10)
    assert sum(s.dur for s in to_host + puts) == \
        pytest.approx(upload[0].dur, rel=0.10)


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
