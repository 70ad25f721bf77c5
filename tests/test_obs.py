"""The storage-native telemetry stack: registry, tracer, flight recorder,
and the ``obs``/``top`` ops surface.

The headline assertion lives in ``test_top_renders_dead_producer``: a
producer runs in a *separate process*, exits without any shutdown handshake,
and the operator CLI still renders its throughput/conflict counters purely
from the snapshots it published to the object store.
"""
import io
import json
import os
import subprocess
import sys
import time

import pytest

import repro
from repro.core import (FaultPolicy, FaultyObjectStore, MemoryObjectStore,
                        Namespace, Producer, Reclaimer, Watermark,
                        write_watermark)
from repro.core.stats import percentile
from repro.obs.recorder import (FlightRecorder, _snap_key, component_dirs,
                                latest_snapshot, list_snaps, prune_snaps,
                                read_snapshots)
from repro.obs.registry import (COUNTER, GAUGE, HISTOGRAM, MetricsRegistry,
                                StatsView, default_registry,
                                set_default_registry)
from repro.obs.tracer import (TRACER, disable_tracing, enable_tracing,
                              trace_span)
from repro.ops.obs import component_summary, obs_summary, render_top


@pytest.fixture
def reg():
    """Isolate the process default registry per test and restore it after."""
    fresh = MetricsRegistry()
    prev = set_default_registry(fresh)
    yield fresh
    set_default_registry(prev)


class VStats(StatsView):
    """Minimal spec'd view for registry plumbing tests."""

    _FAMILY = "vtest"
    _SPEC = {"n": COUNTER, "level": GAUGE, "lat": HISTOGRAM}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_scope_collision_gets_suffixed():
    r = MetricsRegistry()
    assert r.scope("producer.p0") == "producer.p0"
    assert r.scope("producer.p0") == "producer.p0#2"
    assert r.scope("producer.p0") == "producer.p0#3"
    assert r.scope("producer.p1") == "producer.p1"


def test_duplicate_metric_name_rejected():
    r = MetricsRegistry()
    r.counter("a.b.c")
    with pytest.raises(ValueError, match="already registered"):
        r.counter("a.b.c")
    with pytest.raises(ValueError, match="already registered"):
        r.histogram("a.b.c")
    r.histogram("a.b.h")
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("a.b.h")


def test_snapshot_prefix_filter_and_components():
    r = MetricsRegistry()
    r.counter("consumer.d0c0.steps").value = 3
    r.counter("consumer.d1c0.steps").value = 5
    r.histogram("producer.p0.lat").append(0.5)
    snap = r.snapshot("consumer.d0c0.")
    assert snap == {"consumer.d0c0.steps": 3}
    assert r.components() == ["consumer.d0c0", "consumer.d1c0", "producer.p0"]
    full = r.snapshot()
    assert full["producer.p0.lat"]["count"] == 1
    json.dumps(full)  # the recorder payload must be JSON-stable


def test_histogram_summary_matches_shared_percentiles():
    r = MetricsRegistry()
    h = r.histogram("x.y.lat", maxlen=64)
    vals = [float(i) for i in range(50)]
    for v in vals:
        h.append(v)
    s = h.summary()
    assert s["count"] == 50 and s["sum"] == pytest.approx(sum(vals))
    for p in (50, 95, 99):
        assert s[f"p{p}"] == pytest.approx(percentile(vals, float(p)))


def test_histogram_exact_count_beyond_bounded_tail():
    r = MetricsRegistry()
    h = r.histogram("x.y.lat", maxlen=8)
    for v in range(100):
        h.append(float(v))
    s = h.summary()
    # count/sum are exact over everything ever appended; percentiles are
    # over the bounded tail (the newest 8 samples: 92..99)
    assert s["count"] == 100
    assert s["sum"] == pytest.approx(sum(range(100)))
    assert s["p50"] == pytest.approx(percentile(list(range(92, 100)), 50.0))


def test_empty_histogram_summary_is_null_not_nan():
    r = MetricsRegistry()
    s = r.histogram("x.y.lat").summary()
    assert s == {"count": 0, "sum": 0.0, "p50": None, "p95": None,
                 "p99": None}
    json.dumps(s)


# ---------------------------------------------------------------------------
# StatsView write-through
# ---------------------------------------------------------------------------

def test_statsview_write_through():
    r = MetricsRegistry()
    v = VStats("a", registry=r)
    v.n += 1
    v.n += 1
    v.level = 7.5
    v.lat.append(0.25)
    assert v.n == 2 and v.level == 7.5
    assert r.get("vtest.a.n") == 2
    assert r.get("vtest.a.level") == 7.5
    assert r.get("vtest.a.lat")["count"] == 1
    assert v.metric_scope == "vtest.a"
    assert v.snapshot()["n"] == 2


def test_statsview_histogram_assignment_rejected():
    v = VStats("b", registry=MetricsRegistry())
    with pytest.raises(AttributeError, match="histogram"):
        v.lat = [1, 2, 3]
    v.lat.append(1.0)  # the supported mutation
    assert len(v.lat) == 1


def test_statsview_unknown_attribute_raises():
    v = VStats("c", registry=MetricsRegistry())
    with pytest.raises(AttributeError):
        v.no_such_field
    v.helper = "ok"  # non-spec'd attributes behave normally
    assert v.helper == "ok"


def test_statsview_instances_never_alias():
    r = MetricsRegistry()
    a = VStats("same", registry=r)
    b = VStats("same", registry=r)
    a.n += 1
    assert b.n == 0
    assert a.metric_scope != b.metric_scope
    assert b.metric_scope == "vtest.same#2"


def test_statsview_uses_default_registry(reg):
    v = VStats("d")
    v.n += 1
    assert reg.get("vtest.d.n") == 1
    assert default_registry() is reg


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_disabled_is_shared_noop():
    disable_tracing()
    TRACER.clear()
    assert trace_span("a", cat="x") is trace_span("b", cat="y")
    with trace_span("consumer.fetch", cat="read"):
        pass
    assert len(TRACER) == 0


def test_tracer_nesting_and_chrome_roundtrip(tmp_path):
    enable_tracing()
    TRACER.clear()
    try:
        with trace_span("outer", cat="read", step=3):
            with trace_span("inner", cat="read"):
                pass
        with trace_span("train.step", cat="compute"):
            pass
    finally:
        disable_tracing()
    spans = TRACER.spans()
    assert [s.name for s in spans] == ["inner", "outer", "train.step"]
    inner, outer = spans[0], spans[1]
    assert inner.t0 >= outer.t0
    assert inner.t0 + inner.dur <= outer.t0 + outer.dur + 1e-6
    assert outer.args == {"step": 3}

    path = str(tmp_path / "trace.json")
    assert TRACER.write_chrome_trace(path) == 3
    doc = json.loads(open(path).read())
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"X"}
    by_name = {e["name"]: e for e in evs}
    assert by_name["outer"]["args"] == {"step": 3}
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]

    report = TRACER.stall_report()
    assert "outer" in report and "data-plane" in report
    TRACER.clear()


def test_tracer_records_spans_that_raise():
    enable_tracing()
    TRACER.clear()
    try:
        with pytest.raises(RuntimeError):
            with trace_span("commit.cput", cat="commit"):
                raise RuntimeError("5xx")
    finally:
        disable_tracing()
    assert [s.name for s in TRACER.spans()] == ["commit.cput"]
    TRACER.clear()


def test_spans_join_the_profiler_trace_on_its_clock(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData
    disable_tracing()
    TRACER.clear()
    with jax.profiler.trace(str(tmp_path)):
        with trace_span("obs.untraced", cat="read"):
            pass
        enable_tracing()
        try:
            with trace_span("obs.outer", cat="read", step=3):
                with trace_span("obs.inner", cat="read"):
                    time.sleep(0.002)
        finally:
            disable_tracing()
    spans = {s.name: s for s in TRACER.spans()}
    TRACER.clear()
    assert set(spans) == {"obs.outer", "obs.inner"}
    outer, inner = spans["obs.outer"], spans["obs.inner"]
    assert outer.parent is None and inner.parent == outer.id != inner.id

    data = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    env = next(p for p in data.planes if p.name == "Task Environment")
    base = dict(env.stats)["profile_start_time"]   # ns, realtime clock
    starts = {ev.name: base + ev.start_ns
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    assert "obs.untraced" not in starts   # no mirror with the tracer off
    for s in (outer, inner):
        assert abs(starts[s.name] - s.t0 * 1e9) < 200e3, s.name


def test_span_opened_on_another_thread_names_its_parent():
    import threading

    from repro.obs.tracer import self_times

    enable_tracing()
    TRACER.clear()
    try:
        with trace_span("checkpoint.upload", cat="checkpoint") as up:
            parent = TRACER.current()

            def work(leaf):
                with trace_span("checkpoint.put", cat="checkpoint",
                                parent=parent, leaf=leaf):
                    with trace_span("store.io", cat="checkpoint"):
                        time.sleep(0.01)

            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert not any(t.is_alive() for t in threads)
        assert TRACER.current() is None
    finally:
        disable_tracing()
    spans = TRACER.spans()
    TRACER.clear()
    assert parent == up.id
    puts = [s for s in spans if s.name == "checkpoint.put"]
    upload = next(s for s in spans if s.name == "checkpoint.upload")
    assert len(puts) == 2 and all(s.parent == upload.id for s in puts)
    assert all(s.tid != upload.tid for s in puts)
    # a span nested on the worker takes the worker's open span as parent
    ios = [s for s in spans if s.name == "store.io"]
    assert sorted(s.parent for s in ios) == sorted(s.id for s in puts)
    # overlapping children on other threads leave the parent's self time
    own = self_times(spans)
    assert own[upload.id] == upload.dur
    assert all(own[s.id] < s.dur for s in puts)


def test_stall_report_counts_nested_spans_once():
    enable_tracing()
    TRACER.clear()
    try:
        with trace_span("pipeline.compute", cat="compute", step=0):
            with trace_span("pipeline.dispatch", cat="compute", step=0):
                time.sleep(0.01)
            with trace_span("pipeline.sync", cat="compute", step=0):
                time.sleep(0.01)
        with trace_span("pipeline.data_wait", cat="read", step=1):
            with trace_span("consumer.wait", cat="read", step=1):
                time.sleep(0.01)
        # a wait on the staging thread's fetch is off the critical path
        with trace_span("pipeline.stage.fetch", cat="prefetch", step=2):
            with trace_span("consumer.wait", cat="read", step=2):
                time.sleep(0.01)
    finally:
        disable_tracing()
    spans = {s.name: s for s in TRACER.spans()}
    report = TRACER.stall_report()
    TRACER.clear()
    compute_ms = spans["pipeline.compute"].dur * 1e3
    wait_ms = spans["pipeline.data_wait"].dur * 1e3
    line = next(ln for ln in report.splitlines()
                if ln.startswith("data-plane wait"))
    words = line.split()
    assert float(words[2]) == pytest.approx(wait_ms, abs=0.011)
    assert float(words[6]) == pytest.approx(compute_ms, abs=0.011)
    cat = next(ln for ln in report.splitlines()
               if ln.startswith("category compute"))
    assert float(cat.split()[2]) == pytest.approx(compute_ms, abs=0.011)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _recorder(ns, reg, instance="a", **kw):
    v = VStats(instance, registry=reg)
    rec = FlightRecorder(ns, v.metric_scope, interval_s=0.0, registry=reg,
                         **kw)
    return v, rec


def test_snap_chain_and_latest(ns):
    reg = MetricsRegistry()
    v, rec = _recorder(ns, reg)
    v.n += 1
    assert rec.snap()
    v.n += 4
    assert rec.snap()
    assert list_snaps(ns, "vtest.a") == [0, 1]
    snaps = read_snapshots(ns, "vtest.a")
    assert [s["seq"] for s in snaps] == [0, 1]
    assert snaps[0]["metrics"]["vtest.a.n"] == 1
    last = latest_snapshot(ns, "vtest.a")
    assert last["seq"] == 1 and last["metrics"]["vtest.a.n"] == 5
    assert last["inc"] == snaps[0]["inc"]
    assert component_dirs(ns) == ["vtest.a"]


def test_maybe_snap_interval_gating(ns):
    reg = MetricsRegistry()
    _, rec = _recorder(ns, reg)
    rec.interval_s = 3600.0
    assert rec.maybe_snap() is True    # first heartbeat always publishes
    assert rec.maybe_snap() is False   # interval not elapsed
    assert rec.published == 1
    assert rec.close()                 # shutdown forces a final snapshot
    assert list_snaps(ns, "vtest.a") == [0, 1]


def test_recorder_rejects_bad_component():
    with pytest.raises(ValueError):
        FlightRecorder(Namespace(MemoryObjectStore(), "r"), "a/b")
    with pytest.raises(ValueError):
        FlightRecorder(Namespace(MemoryObjectStore(), "r"), "")


def test_snap_never_raises_under_faults():
    inner = MemoryObjectStore()
    store = FaultyObjectStore(inner, FaultPolicy(
        seed=3, cput_error_rate=1.0, cput_lost_ack_rate=0.0,
        key_filter=".snap", max_faults=3))
    ns = Namespace(store, "runs/test")
    reg = MetricsRegistry()
    v, rec = _recorder(ns, reg)
    v.n += 1
    assert rec.snap() is False         # injected cput error, swallowed
    assert rec.dropped >= 1
    for _ in range(10):                # burn through max_faults, then land
        if rec.snap():
            break
    assert rec.published >= 1
    snaps = read_snapshots(ns, rec.component)
    assert snaps and snaps[-1]["metrics"][f"{rec.component}.n"] == 1


def test_snap_survives_lost_ack():
    # the ambiguous outcome: the put landed server-side, then "failed".
    # The recorder counts a drop, but the chain stays readable and the next
    # snap claims the next free seq instead of colliding forever.
    inner = MemoryObjectStore()
    store = FaultyObjectStore(inner, FaultPolicy(
        seed=0, cput_error_rate=1.0, cput_lost_ack_rate=1.0,
        key_filter=".snap", max_faults=1))
    ns = Namespace(store, "runs/test")
    reg = MetricsRegistry()
    v, rec = _recorder(ns, reg)
    assert rec.snap() is False and rec.dropped == 1
    assert rec.snap() is True
    seqs = list_snaps(ns, rec.component)
    assert seqs == sorted(set(seqs))   # no overwrites, chain intact
    assert len(read_snapshots(ns, rec.component)) == len(seqs)


def test_torn_snapshot_skipped(ns):
    reg = MetricsRegistry()
    v, rec = _recorder(ns, reg)
    assert rec.snap()
    # a torn write lands between two good snapshots
    ns.store.put(_snap_key(ns, rec.component, 1), b"{torn")
    rec._next_seq = None               # recorder re-lists past the garbage
    v.n += 1
    assert rec.snap()
    snaps = read_snapshots(ns, rec.component)
    assert [s["seq"] for s in snaps] == [0, 2]
    # wrong-schema docs are skipped too
    ns.store.put(_snap_key(ns, rec.component, 3),
                 json.dumps({"schema": 99, "seq": 3}).encode())
    assert [s["seq"] for s in read_snapshots(ns, rec.component)] == [0, 2]


def test_two_incarnations_interleave(ns):
    reg = MetricsRegistry()
    v = VStats("a", registry=reg)
    r1 = FlightRecorder(ns, v.metric_scope, interval_s=0.0, registry=reg)
    r2 = FlightRecorder(ns, v.metric_scope, interval_s=0.0, registry=reg)
    assert r1.incarnation != r2.incarnation
    assert r1.snap() and r2.snap() and r1.snap()
    snaps = read_snapshots(ns, v.metric_scope)
    assert [s["seq"] for s in snaps] == [0, 1, 2]
    assert [s["inc"] for s in snaps] == \
        [r1.incarnation, r2.incarnation, r1.incarnation]


def test_prune_snaps_keeps_newest(ns):
    reg = MetricsRegistry()
    v, rec = _recorder(ns, reg)
    for i in range(12):
        v.n += 1
        assert rec.snap()
    assert prune_snaps(ns, keep=8) == 4
    assert list_snaps(ns, rec.component) == list(range(4, 12))
    assert latest_snapshot(ns, rec.component)["metrics"][
        f"{rec.component}.n"] == 12


def test_reclaimer_prunes_obs_snaps(ns, reg):
    v, rec = _recorder(ns, reg)
    for _ in range(6):
        assert rec.snap()
    write_watermark(ns, 0, Watermark(version=0, step=0))
    r = Reclaimer(ns, expected_ranks=1, obs_keep_snaps=2)
    assert r.run_cycle() is not None
    assert r.stats.obs_snaps_deleted == 4
    assert list_snaps(ns, rec.component) == [4, 5]


# ---------------------------------------------------------------------------
# the obs/top read surface
# ---------------------------------------------------------------------------

class CStats(StatsView):
    _FAMILY = "consumer"
    _SPEC = {"steps_consumed": COUNTER, "bytes_consumed": COUNTER}


def test_component_summary_rates_and_lag(ns):
    reg = MetricsRegistry()
    v = CStats("d0c0", registry=reg)
    rec = FlightRecorder(ns, v.metric_scope, interval_s=0.0, registry=reg)
    v.steps_consumed, v.bytes_consumed = 2, 2048
    assert rec.snap()
    time.sleep(0.01)
    v.steps_consumed, v.bytes_consumed = 3, 3072
    assert rec.snap()
    row = component_summary(ns, "consumer.d0c0",
                            frontier={"version": 4, "total_steps": 10})
    assert row["family"] == "consumer" and row["snaps"] == 2
    assert row["metrics"]["steps_consumed"] == 3
    assert row["lag_steps"] == 7
    assert row["steps_per_s"] == pytest.approx(
        row["rates"]["steps_consumed_per_s"])
    assert row["steps_per_s"] > 0
    assert row["throughput_Bps"] == pytest.approx(
        row["rates"]["bytes_consumed_per_s"])


def test_rates_never_cross_incarnations(ns):
    reg = MetricsRegistry()
    v = CStats("d0c0", registry=reg)
    r1 = FlightRecorder(ns, v.metric_scope, interval_s=0.0, registry=reg)
    v.steps_consumed = 5
    assert r1.snap()
    # restart: the counter resets in a new incarnation; differencing across
    # the restart would yield a negative rate
    reg2 = MetricsRegistry()
    v2 = CStats("d0c0", registry=reg2)
    r2 = FlightRecorder(ns, v2.metric_scope, interval_s=0.0, registry=reg2)
    v2.steps_consumed = 1
    assert r2.snap()
    row = component_summary(ns, "consumer.d0c0")
    assert row["rates"] == {}  # only one snapshot of the latest incarnation


def test_obs_summary_empty_namespace(ns):
    s = obs_summary(ns)
    assert s["frontier"] is None and s["components"] == []
    buf = io.StringIO()
    render_top(s, buf)
    assert "no telemetry snapshots" in buf.getvalue()


def test_obs_summary_recurses_streams(ns, reg):
    v, rec = _recorder(ns, reg, instance="root")
    assert rec.snap()
    sns = ns.stream("filtered")
    v2 = VStats("sub", registry=reg)
    rec2 = FlightRecorder(sns, v2.metric_scope, interval_s=0.0, registry=reg)
    assert rec2.snap()
    s = obs_summary(ns)
    assert [c["component"] for c in s["components"]] == ["vtest.root"]
    assert [c["component"] for c in s["streams"]["filtered"]["components"]] \
        == ["vtest.sub"]


# ---------------------------------------------------------------------------
# post-mortem: a dead producer renders from storage alone
# ---------------------------------------------------------------------------

_PRODUCER_SCRIPT = """
import os
from repro.core import FileObjectStore, Namespace, Producer
ns = Namespace(FileObjectStore({root!r}), "runs/pm")
p = Producer(ns, "p0", dp=1, cp=1, obs_snap_interval_s=0.0)
p.recover()
for i in range(5):
    p.write_tgb(slice_payloads={{(0, 0): bytes([i]) * 64}})
    p.maybe_commit(force=True)
os._exit(0)  # hard exit: no finalize, no close, no goodbye snapshot
"""


def test_top_renders_dead_producer(tmp_path):
    """The acceptance demo: the producing process is *gone* (hard-exited in
    a subprocess) and ``batchweave top``/``obs --json`` still reconstruct
    its counters purely from object-store snapshots."""
    root = str(tmp_path / "store")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(repro.__file__))
    proc = subprocess.run(
        [sys.executable, "-c", _PRODUCER_SCRIPT.format(root=root)],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr

    from repro.ops.cli import main as ops_main
    buf = io.StringIO()
    assert ops_main(["--root", root, "-n", "runs/pm", "top"], out=buf) == 0
    top = buf.getvalue()
    assert "producer.p0" in top and "total_steps=5" in top

    buf = io.StringIO()  # NB: the global --json flag precedes the subcommand
    assert ops_main(["--root", root, "-n", "runs/pm", "--json", "obs"],
                    out=buf) == 0
    doc = json.loads(buf.getvalue())
    rows = {r["component"]: r for r in doc["components"]}
    row = rows["producer.p0"]
    assert row["metrics"]["tgbs_written"] == 5
    assert row["metrics"]["commit_successes"] >= 4
    assert row["conflict_rate"] == 0.0
    assert doc["frontier"]["total_steps"] == 5


def test_live_producer_consumer_snapshots(ns, reg):
    """In-process end-to-end: producer + consumer publish through their
    natural heartbeats and obs_summary sees both families."""
    from repro.core import Consumer, MeshPosition
    p = Producer(ns, "p0", dp=1, cp=1, obs_snap_interval_s=0.0)
    p.recover()
    for i in range(4):
        p.write_tgb(slice_payloads={(0, 0): bytes([i]) * 32})
        p.maybe_commit(force=True)
    p.finalize()
    c = Consumer(ns, MeshPosition(0, 0, 1, 1), obs_snap_interval_s=0.0)
    for _ in range(3):
        c.next_batch(timeout_s=5.0)
    s = obs_summary(ns)
    rows = {r["component"]: r for r in s["components"]}
    assert rows["producer.p0"]["metrics"]["tgbs_written"] == 4
    assert rows["consumer.d0c0"]["metrics"]["steps_consumed"] == 3
    assert rows["consumer.d0c0"]["lag_steps"] == 1


def test_a_span_notes_arguments_learnt_inside_it():
    tracer = enable_tracing()
    tracer.clear()
    try:
        with trace_span("pipeline.sync", cat="compute", step=3) as sp:
            sp.note(moe_rows_local=12, moe_rows_max=5)
    finally:
        disable_tracing()
    (span,) = [s for s in tracer.spans() if s.name == "pipeline.sync"]
    tracer.clear()
    assert span.args == {"step": 3, "moe_rows_local": 12, "moe_rows_max": 5}
    with trace_span("pipeline.sync", step=4) as sp:   # disabled: a no-op
        sp.note(moe_rows_local=1)
    assert tracer.spans() == []
