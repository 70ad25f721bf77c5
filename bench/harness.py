"""The general driver of one cell: set-up, the measured window, recovery,
and the checks that decide ``correct``.

A cell is a configuration file (``configs/<config>.json``: the model cut,
the step, the data-plane deployment, the store model, the checkpoint
cadence) under a traffic file (``traffic/<traffic>.json``: the token mix the
producers commit and what happens after the window). Nothing here names a
cell; every difference between cells is in those two files.

Set-up builds one trainer, the jitted step with its state and the
``FusedTrainLoop`` fed by ``TrainSession`` readers while producer threads
commit the benchmark's own grids, and drives it through its first
``check_steps`` steps. Those steps compile the step and are what the
reference follows. The same trainer then runs the window. A configuration
with ``checkpoint_every`` saves first and then trains that many steps, round
after round, through ``FusedTrainLoop.aligned_checkpoint``.

The model is the configuration's too: its ``"reference"`` key names the
module of ``bench/reference/`` that gives the program's config, the weights,
the reference step and the model FLOPs (``reference``).
"""
from __future__ import annotations

import importlib
import json
import threading
import time
import traceback
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

from bench import store as bench_store
from bench.reference import CONTRACT
from bench.reference import dataplane as ref_data

ROOT = Path(__file__).resolve().parent
NAMESPACE = "runs/bench"


@dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    chips: int


def load_cell(checkout: Path, name: str) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files read."""
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((checkout / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (ROOT / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, config, traffic, int(w["chips"]))


@dataclass
class Hooks:
    """Where a test plants a fault in the timed path. The benchmark's own
    runs leave every hook at its default."""

    #: wraps the jitted step: ``wrap_step(step_fn, config) -> step_fn``
    wrap_step: Optional[Callable] = None
    #: the grid a producer commits: ``grid(generator, producer, seq)``
    grid: Optional[Callable] = None


@dataclass
class Run:
    """What one run recorded, for the checks and the per-layer readers."""

    setup_s: float = 0.0
    window_s: float = 0.0
    window_tokens: int = 0
    reports: List = field(default_factory=list)      # FusedReport per call
    stalls_s: List[float] = field(default_factory=list)
    restore_s: Optional[float] = None
    resume_s: Optional[float] = None
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0
    spans: List = field(default_factory=list)        # repro.obs spans
    staged_batches: int = 0
    trace: Optional[Dict] = None                     # trace_reduce output
    checks: Dict[str, Tuple[float, float]] = field(default_factory=dict)
    #: the program's and the reference's readings of the checked steps, and
    #: the TGBs ``(producer, seq)`` those steps trained on
    prog: Dict = field(default_factory=dict)
    ref: Dict = field(default_factory=dict)
    check_ids: List = field(default_factory=list)
    config: Dict = field(default_factory=dict)
    device_kind: str = ""


class CompileCounter:
    """Counts lowerings (each jit trace that is compiled or looked up in the
    cache) while registered."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.lowerings = 0

    def _on_duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == self._LOWER:
            self.lowerings += 1

    def __enter__(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc) -> bool:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        return False


# ---------------------------------------------------------------------------
# the program, as a configuration states it
# ---------------------------------------------------------------------------

def reference(cfg: Mapping):
    """The module ``bench.reference.<cfg["reference"]>``, imported once
    (``sys.modules`` keeps it); ``SystemExit`` where the configuration names
    none, or one that is missing or lacks a name of the contract."""
    name = cfg.get("reference")
    where = f"configuration {cfg.get('name')!r}"
    if not isinstance(name, str) or not name.isidentifier():
        raise SystemExit(f"bench: {where} names no reference module: give "
                         f"it a \"reference\" key naming "
                         f"bench/reference/<name>.py")
    module = f"bench.reference.{name}"
    try:
        mod = importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise SystemExit(f"bench: {where} names the reference {name!r}, "
                         f"but there is no bench/reference/{name}.py")
    missing = [n for n in CONTRACT if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"bench: bench/reference/{name}.py, the reference "
                         f"of {where}, lacks {missing}")
    return mod


def model_config(cfg: Mapping):
    return reference(cfg).program_config(cfg)


def optimizer_config(cfg: Mapping):
    from repro.train.optimizer import OptimizerConfig
    return OptimizerConfig(state_dtype=cfg["precision"]["optimizer_state"],
                           **cfg["optimizer"])


def topology(cfg: Mapping):
    from repro.dataplane import Topology
    t, dp = cfg["train"], cfg["data_plane"]
    return Topology(dp=dp["dp"], cp=dp["cp"], global_batch=t["global_batch"],
                    seq_len=t["seq_len"])


def make_step(cfg: Mapping, microbatches: Optional[int] = None,
              donate: bool = True):
    """The program's jitted train step, params and optimizer state donated."""
    import jax
    from repro.train.step import StepConfig, make_train_step
    step = make_train_step(model_config(cfg), optimizer_config(cfg),
                           StepConfig(microbatches=microbatches
                                      or cfg["train"]["microbatches"]))
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def make_state(cfg: Mapping, seed: int):
    """Params from the seed and a zero optimizer state, on the device, each
    in one jitted call."""
    import jax
    from repro.train.optimizer import init_opt_state
    ref = reference(cfg)
    init = jax.jit(ref.make_init(cfg["model"]))
    params = init(ref.seed_words(seed))
    dt = cfg["precision"]["optimizer_state"]
    opt = jax.jit(lambda p: init_opt_state(p, dt))(params)
    return params, opt


def abstract_state(cfg: Mapping):
    import jax
    from repro.train.optimizer import init_opt_state
    ref = reference(cfg)
    params = jax.eval_shape(ref.make_init(cfg["model"]), ref.seed_words(0))
    dt = cfg["precision"]["optimizer_state"]
    return {"params": params,
            "opt": jax.eval_shape(lambda p: init_opt_state(p, dt), params)}


# ---------------------------------------------------------------------------
# producers
# ---------------------------------------------------------------------------

class Producers:
    """Producer threads committing the generator's grids through the
    session's writers, each TGB flushed as it is written, pausing while the
    committed total is ``lead`` global steps ahead of the frontier."""

    def __init__(self, session, gen: ref_data.TokenGenerator, count: int,
                 lead: int, frontier: Callable[[], int],
                 grid: Optional[Callable] = None):
        from repro.core.dac import DACPolicy
        self.written = {p: 0 for p in range(count)}
        self.errors: List[BaseException] = []
        self._stop = threading.Event()
        self._cond = threading.Condition()
        topo = session.topology
        rows = topo.global_batch // topo.dp
        make = grid or (lambda g, p, s: g.grid(p, s))

        def produce(pid: int) -> None:
            try:
                with session.writer(f"w{pid}", policy=DACPolicy()) as w:
                    while not self._stop.is_set():
                        if sum(self.written.values()) >= frontier() + lead:
                            self._stop.wait(0.005)
                            continue
                        g = make(gen, pid, self.written[pid])
                        slices = {(d, 0): np.ascontiguousarray(
                            g[d * rows:(d + 1) * rows]).tobytes()
                            for d in range(topo.dp)}
                        w.write(slices, num_samples=g.shape[0],
                                token_count=g.size)
                        # a commit can lose the manifest race to the other
                        # producer; retry until the TGB is committed
                        while w.producer.pending and not w.flush():
                            if self._stop.wait(0.002):
                                return
                        with self._cond:
                            self.written[pid] += 1
                            self._cond.notify_all()
            except BaseException as e:   # surfaced by wait_for / stop
                traceback.print_exc()
                self.errors.append(e)
                with self._cond:
                    self._cond.notify_all()

        self.threads = [threading.Thread(target=produce, args=(p,),
                                         daemon=True, name=f"producer-{p}")
                        for p in range(count)]
        for t in self.threads:
            t.start()

    def wait_for(self, total: int, timeout_s: float = 120.0) -> None:
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while sum(self.written.values()) < total:
                if self.errors:
                    raise self.errors[0]
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"producers committed "
                                       f"{self.written} of {total}")
                self._cond.wait(min(left, 0.1))

    def stop(self) -> None:
        """Stop and join every thread; raises the first producer error."""
        self._stop.set()
        for t in self.threads:
            t.join(timeout=60.0)
        alive = [t.name for t in self.threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"producer threads did not stop: {alive}")
        if self.errors:
            raise self.errors[0]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _annotate(name: str, on: bool):
    if not on:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, limits: Mapping[str, float],
             hooks: Hooks = Hooks(), trace_dir: Optional[Path] = None
             ) -> Run:
    """Set up, measure for ``seconds``, recover where the traffic says, and
    check. ``t_start`` is when the process began (set-up counts from it).
    ``limits`` holds each compared number's limit."""
    import jax
    from repro.obs import tracer as obs
    from repro.run import TrainSession
    from repro.train.pipeline import FusedTrainLoop, ReaderFanInSource

    cfg, traffic = cell.config, cell.traffic
    ref_model = reference(cfg)
    dp_cfg, train = cfg["data_plane"], cfg["train"]
    topo = topology(cfg)
    run = Run(config=cfg, device_kind=jax.devices()[0].device_kind)
    gen = ref_data.TokenGenerator(seed, cfg["model"]["vocab_size"],
                                  train["global_batch"], train["seq_len"],
                                  traffic["zipf_s"])
    store = bench_store.ModelledStore(cfg["store"], seed)
    session = TrainSession(store, topo, namespace=NAMESPACE)
    consumed: Dict[int, bytes] = {}
    losses: Dict[int, float] = {}

    def frontier() -> int:
        return max(consumed, default=-1) + 1

    def record(step: int, tokens: np.ndarray) -> None:
        consumed[step] = tokens.tobytes()

    producers = Producers(session, gen, dp_cfg["producers"],
                          dp_cfg["producer_lead"], frontier, hooks.grid)

    def fan_in(sess):
        return ReaderFanInSource(
            [sess.reader(dp_rank=d, prefetch_depth=dp_cfg["reader_prefetch"])
             for d in range(topo.dp)], topo)

    def train_steps(loop, n: int, label: str) -> None:
        for _ in range(n):
            with _annotate(label, trace):
                rep = loop.run(1, on_batch=record)
            run.reports.append(rep)
            for t in rep.timings:
                losses[t.step] = t.loss

    step_fn = make_step(cfg)
    if hooks.wrap_step is not None:
        step_fn = hooks.wrap_step(step_fn, cfg)
    change_fn = ref_model.change_norms(cfg["model"])
    words = ref_model.seed_words(seed)
    check_steps = traffic["check_steps"]
    loop = None
    try:
        params, opt = make_state(cfg, seed)
        paths = ref_model.leaf_paths(params)
        loop = FusedTrainLoop(fan_in(session), step_fn, params, opt,
                              topology=topo, depth=dp_cfg["ring_depth"])
        del params, opt
        producers.wait_for(dp_cfg["producer_lead"])
        b1 = cfg["optimizer"]["b1"]
        prog: Dict[str, object] = {}
        with loop:
            # the first steps: compile, and give the reference its readings
            train_steps(loop, 1, "bench.setup")
            prog["grad"] = dict(zip(paths, [
                float(x) / (1.0 - b1)
                for x in ref_model.leaf_norms(loop.opt_state["m"])]))
            train_steps(loop, check_steps - 1, "bench.setup")
            prog["change"] = dict(zip(paths, [
                float(x) for x in change_fn(loop.params, words)]))
            prog["losses"] = [losses[s] for s in range(check_steps)]
            run.reports.clear()
            every = cfg.get("checkpoint_every")
            if trace:
                obs.enable_tracing(capacity=1 << 20)
                obs.TRACER.clear()
                jax.profiler.start_trace(str(trace_dir))
            staged0 = loop.stats.staged_batches
            run.setup_s = time.perf_counter() - t_start
            with CompileCounter() as window, \
                    _annotate("bench.window", trace):
                t0 = time.perf_counter()
                while True:
                    if every:
                        ts = time.perf_counter()
                        with _annotate("bench.checkpoint", trace):
                            loop.aligned_checkpoint(
                                session, {"params": loop.params,
                                          "opt": loop.opt_state})
                        run.stalls_s.append(time.perf_counter() - ts)
                        train_steps(loop, every, "bench.step")
                    else:
                        train_steps(loop, 1, "bench.step")
                    if time.perf_counter() - t0 >= seconds:
                        break
                run.window_s = time.perf_counter() - t0
            run.compiles_in_window = window.lowerings
            run.staged_batches = loop.stats.staged_batches - staged0
            if trace:
                jax.profiler.stop_trace()
                run.spans = obs.TRACER.spans()
                obs.disable_tracing()
            run.window_tokens = sum(r.tokens for r in run.reports)
            if traffic["resume"]:
                replay = _resume(run, cfg, loop, session, store, step_fn,
                                 fan_in, traffic["replay_steps"])
                loop = None
        run.memory_peak_bytes = _memory_peak()
        window_end = frontier()
    finally:
        producers.stop()
        if loop is not None:
            loop.stop()
            loop.params = loop.opt_state = None
        session.close()

    # -- checks: after the window, the program's state freed ----------------
    grids = [consumed[s] for s in range(window_end)]
    dp_check = ref_data.check_consumed(gen, grids, producers.written)
    checks: Dict[str, Tuple[float, float]] = {}
    checks["grids_wrong"] = (float(dp_check["unknown"]
                                   + dp_check["duplicated"]
                                   + dp_check["out_of_order"]),
                             limits["grids_wrong"])
    checks["compiles_in_window"] = (float(run.compiles_in_window),
                                    limits["compiles_in_window"])
    if traffic["resume"]:
        got, want = replay
        checks["replay_grids_wrong"] = (
            float(sum(g != consumed.get(s) for s, (g, _) in got.items())),
            limits["replay_grids_wrong"])
        checks["replay_loss_gap"] = (max(
            abs(l - want[s]) / abs(want[s]) for s, (_, l) in got.items()),
            limits["replay_loss_gap"])
    ids = dp_check["ids"][:check_steps]
    run.prog, run.check_ids = prog, ids
    if all(i is not None for i in ids):
        run.ref = ref_model.reference_steps(
            cfg["model"], cfg["optimizer"], seed,
            [gen.grid(*i) for i in ids])
        checks.update(compare_steps(prog, run.ref, limits))
    else:
        for name in ("loss_gap", "grad_gap", "change_gap"):
            checks[name] = (float("inf"), limits[name])
    run.checks = checks
    return run


def _resume(run: Run, cfg, loop, session, store, step_fn, fan_in,
            replay_steps: int):
    """Drop the trainer as if killed, resume from the last aligned
    checkpoint, and train ``replay_steps`` steps; ``run.resume_s`` runs
    until the first resumed loss is on the host. Returns the replayed
    ``{step: (grid bytes, loss)}`` and the window's losses to hold them to."""
    from repro.run import TrainSession
    from repro.train.pipeline import FusedTrainLoop
    want = {}
    for rep in run.reports:
        for t in rep.timings:
            want[t.step] = t.loss
    t_kill = time.perf_counter()
    loop.stop()
    loop.params = loop.opt_state = None
    session.close()
    resumed = TrainSession.resume(store, NAMESPACE)
    t = time.perf_counter()
    state = resumed.restore_model(abstract_state(cfg))
    run.restore_s = time.perf_counter() - t
    topo = topology(cfg)
    got: Dict[int, Tuple[bytes, float]] = {}
    grids: Dict[int, bytes] = {}
    new = FusedTrainLoop(fan_in(resumed), step_fn, state["params"],
                         state["opt"], topology=topo,
                         depth=cfg["data_plane"]["ring_depth"])
    del state
    first = resumed.resume_step   # the loop counts its steps from 0
    try:
        with new:
            for i in range(replay_steps):
                rep = new.run(1, on_batch=lambda s, tok: grids.__setitem__(
                    first + s, tok.tobytes()))
                if i == 0:
                    run.resume_s = time.perf_counter() - t_kill
                for ts in rep.timings:
                    got[first + ts.step] = (grids[first + ts.step], ts.loss)
    finally:
        new.params = new.opt_state = None
        resumed.close()
    return got, want


def _memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


# ---------------------------------------------------------------------------
# the comparison with the reference
# ---------------------------------------------------------------------------

def leaf_gap(prog: Mapping[str, float], ref: Mapping[str, float],
             keep: Optional[set] = None) -> float:
    """Worst leaf's gap between two norms, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def compare_steps(prog: Mapping, ref: Mapping, limits: Mapping
                  ) -> Dict[str, Tuple[float, float]]:
    """The step's numbers: the worst step's relative loss gap, the worst
    leaf's gap in the first gradient's norm, and in the norm of the
    parameters' change over the checked steps. Leaves whose reference
    gradient is under a thousandth of the median leaf's move by round-off
    alone and are left out of the change."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    med = float(np.median(list(ref["grad"].values())))
    moved = {n for n, g in ref["grad"].items() if g >= 1e-3 * med}
    return {
        "loss_gap": (loss, limits["loss_gap"]),
        "grad_gap": (leaf_gap(prog["grad"], ref["grad"]), limits["grad_gap"]),
        "change_gap": (leaf_gap(prog["change"], ref["change"], moved),
                       limits["change_gap"]),
    }
