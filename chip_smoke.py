"""One-chip smoke run of the fused training path at Granite-8B widths.

Run from the repository root on a machine with one TPU chip:

    python chip_smoke.py

It takes no options and runs four phases in one process, in order; any
failure raises and the script exits non-zero:

1. device check: exits non-zero unless JAX's first device is a TPU, then
   prints the device and the jax / jaxlib / libtpu versions;
2. compile cache: ``repro.launch.compile_cache.use_compile_cache``;
3. fused training on live streams: two producer threads keep committing
   token batches through ``TrainSession`` writers while ``FusedTrainLoop``
   trains Granite-8B off the session's readers, takes an aligned checkpoint,
   is stopped, and resumes from the checkpoint. Every consumed grid must equal
   a fresh sequential re-read of the stream, and the replay after the resume
   must repeat the recorded grids and losses;
4. the four Pallas kernels at real widths against their ``ref.py`` oracles.

Its last line on standard output is one JSON object naming the device. The
lines before it are one run's readings, not benchmark numbers.
"""
from __future__ import annotations

import dataclasses
import importlib.metadata
import json
import math
import os
import sys
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, Tuple

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import granite_8b  # noqa: E402
from repro.core import MemoryObjectStore  # noqa: E402
from repro.core.dac import DACPolicy  # noqa: E402
from repro.data import (PipelineConfig, PreprocessConfig,  # noqa: E402
                        PreprocessWorker)
from repro.dataplane import Topology, open_dataplane  # noqa: E402
from repro.kernels.common import use_interpret  # noqa: E402
from repro.kernels.decode_attention import decode_attention_fwd  # noqa: E402
from repro.kernels.decode_attention.ref import \
    decode_attention_ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention_fwd  # noqa: E402
from repro.kernels.flash_attention.ref import flash_attention_ref  # noqa: E402
from repro.kernels.rmsnorm import rmsnorm_fwd  # noqa: E402
from repro.kernels.rmsnorm.ref import rmsnorm_ref  # noqa: E402
from repro.kernels.wkv6 import wkv6_fwd  # noqa: E402
from repro.kernels.wkv6.ref import wkv6_ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import ModelConfig, abstract_params  # noqa: E402
from repro.models import init_params, param_specs  # noqa: E402
from repro.run import TrainSession  # noqa: E402
from repro.train.optimizer import OptimizerConfig, init_opt_state  # noqa: E402
from repro.train.pipeline import (FusedTrainLoop,  # noqa: E402
                                  ReaderFanInSource)
from repro.train.step import StepConfig, make_train_step  # noqa: E402

NAMESPACE = "runs/chip_smoke"
SEED = 0             # weights, token data and kernel inputs
WARMUP_STEPS = 2     # absorb the compile and fill the staging ring
TIMED_STEPS = 6      # the readings below
REPLAY_STEPS = 4     # trained after the checkpoint, then replayed after resume
#: producers pause this many global steps ahead of the trainer, so the store
#: holds a bounded backlog while they keep committing through the phase
PRODUCER_LEAD = 16
#: |first loss - ln V|. At init the final norm gives unit-RMS hidden states
#: and the head is fan-in scaled, so logits are ~N(0, 1) and the expected
#: cross-entropy is about ln V + 1/2; a bound of 1 admits logit std up to ~1.4
#: and catches a broken head or embedding scale (std 2 gives ln V + 2).
FIRST_LOSS_BOUND = 1.0
#: the replay runs the same executable on the checkpointed float32 state and
#: the same grids, so its losses agree with the recorded ones to float32
#: rounding (the tolerance tests/test_fused_train.py holds the CPU path to)
REPLAY_LOSS_RTOL = 1e-6


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """The model step and data layout the training phase runs."""

    model: ModelConfig
    topology: Topology
    microbatches: int


#: Granite-8B at its published widths (d_model 4096, 32 query / 8 KV heads of
#: 128, d_ff 14336), cut to one chip's share of a deployment:
#:   * num_layers 2 of 36 -- one stage of an 18-stage pipeline;
#:   * vocab_size 6144 of 49152 -- the chip's share of an 8-way
#:     vocabulary-parallel embedding and head.
#: GB 4 x S 4096 in 4 microbatches is the largest batch at S 4096 whose step
#: fits the 16 GB of one v5e (one microbatch needs 20.19G of 15.75G HBM,
#: two need 19.05G, per the TPU compiler).
CHIP = SmokeConfig(
    model=granite_8b.CONFIG.replace(num_layers=2, vocab_size=6144),
    topology=Topology(dp=2, cp=1, global_batch=4, seq_len=4096),
    microbatches=4)


@dataclasses.dataclass(frozen=True)
class KernelShapes:
    """Input shapes of the kernel phase."""

    flash: Tuple[int, int, int, int, int]     # B, S, H, G, dh
    decode: Tuple[int, int, int, int, int]    # B, T, H, G, dh
    rmsnorm: Tuple[int, ...]                  # x; normalized over the last
    wkv6: Tuple[int, int, int, int]           # B, S, H, dh


#: Granite-8B widths for the attention kernels and rmsnorm (one global
#: batch's hidden state), rwkv6-3b's 40 heads of 64 for wkv6
CHIP_KERNELS = KernelShapes(flash=(1, 4096, 32, 8, 128),
                            decode=(8, 4096, 32, 8, 128),
                            rmsnorm=(4, 4096, 4096),
                            wkv6=(1, 4096, 40, 64))

#: tests/test_kernels.py's bfloat16 tolerances
BF16_TOL = dict(atol=4e-2, rtol=4e-2)
WKV6_Y_TOL = dict(atol=6e-2, rtol=6e-2)
WKV6_STATE_TOL = dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------

def check_device() -> Dict[str, object]:
    """The device JAX found; exits non-zero unless it is a TPU."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform} ({dev.device_kind}); nothing ran")
    import jaxlib
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# phase 3: fused training on live streams
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts lowerings and sums backend-compile seconds while registered."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.lowerings = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == self._LOWER:
            self.lowerings += 1
        elif event == self._COMPILE:
            self.compile_s += duration_secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> bool:
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
        return False


def make_step(sc: SmokeConfig):
    """The jitted train step, params and optimizer state donated."""
    return jax.jit(make_train_step(sc.model, OptimizerConfig(),
                                   StepConfig(microbatches=sc.microbatches)),
                   donate_argnums=(0, 1))


def abstract_state(cfg: ModelConfig):
    """``{"params", "opt"}`` as shapes: the restore template."""
    params = abstract_params(param_specs(cfg))
    return {"params": params, "opt": jax.eval_shape(init_opt_state, params)}


def start_producers(session: TrainSession, pc: PipelineConfig,
                    stop: threading.Event,
                    far_enough_ahead: Callable[[int], bool]
                    ) -> List[threading.Thread]:
    """Two preprocessing workers committing through the session's writers
    until ``stop``; each pauses while ``far_enough_ahead(global steps
    written)`` holds."""
    written = [0, 0]

    def produce(pid: int) -> None:
        with session.writer(f"w{pid}", policy=DACPolicy()) as w:
            worker = PreprocessWorker(pc, PreprocessConfig(), w.producer,
                                      sample_stride=2, sample_offset=pid)
            while not stop.is_set():
                if far_enough_ahead(sum(written)):
                    stop.wait(0.02)
                    continue
                written[pid] += worker.produce_n_tgbs(1, stop=stop)
                w.flush()

    threads = [threading.Thread(target=produce, args=(i,), daemon=True,
                                name=f"producer-{i}") for i in range(2)]
    for t in threads:
        t.start()
    return threads


def sequential_read(store, topo: Topology, steps: int) -> List[bytes]:
    """The plain reference: one fresh reader per rank from the stream start,
    read in order, rows stacked in rank order."""
    ref = open_dataplane(store, topo, backend="tgb", namespace=NAMESPACE)
    readers = [ref.reader(dp_rank=d) for d in range(topo.dp)]
    try:
        return [np.concatenate([r.next_batch(timeout_s=60.0).tokens
                                for r in readers]).tobytes()
                for _ in range(steps)]
    finally:
        ref.close()


def _fan_in(session: TrainSession, topo: Topology) -> ReaderFanInSource:
    return ReaderFanInSource([session.reader(dp_rank=d, prefetch_depth=4)
                              for d in range(topo.dp)], topo)


def train_phase(sc: SmokeConfig) -> Dict[str, object]:
    """Train, checkpoint, stop, resume and replay; returns the readings.

    Raises on any broken check: a consumed grid that differs from the
    sequential re-read, a replay that differs from the recording, a
    non-finite loss, a first loss far from ln V, or a compile inside the
    timed steps.
    """
    cfg, topo = sc.model, sc.topology
    store = MemoryObjectStore()
    session = TrainSession(store, topo, namespace=NAMESPACE)
    pc = PipelineConfig(global_batch=topo.global_batch, seq_len=topo.seq_len,
                        dp=topo.dp, cp=topo.cp, vocab_size=cfg.vocab_size,
                        seed=SEED)
    consumed: List[Tuple[int, bytes]] = []   # (global step, grid bytes)

    def recorder(first_step: int):
        return lambda i, tokens: consumed.append((first_step + i,
                                                  tokens.tobytes()))

    stop = threading.Event()
    producers = start_producers(
        session, pc, stop,
        lambda written: written >= len(consumed) + PRODUCER_LEAD)
    step_fn = make_step(sc)
    out: Dict[str, object] = {}
    try:
        params = init_params(param_specs(cfg), seed=SEED)
        loop = FusedTrainLoop(_fan_in(session, topo), step_fn, params,
                              init_opt_state(params), topology=topo, depth=2)
        del params
        record = recorder(0)
        with loop:
            with CompileCounter() as first:
                warm = loop.run(1, on_batch=record)
            warm2 = loop.run(WARMUP_STEPS - 1, on_batch=record)
            with CompileCounter() as window:
                timed = loop.run(TIMED_STEPS, on_batch=record)
            entry = loop.aligned_checkpoint(
                session, {"params": loop.params, "opt": loop.opt_state})
            after = loop.run(REPLAY_STEPS, on_batch=record)
        out["first_step_compile_s"] = first.compile_s
        out["first_step_cache_hits"] = first.cache_hits
        out["first_step_wall_s"] = warm.timings[0].wall_s
        out["median_step_s"] = float(np.median(
            [t.wall_s for t in timed.timings]))
        out["tokens_per_s"] = timed.tokens_per_s
        out["stall_fractions"] = timed.stall_fractions()
        out["compiles_in_timed_steps"] = window.lowerings
        mem = jax.devices()[0].memory_stats() or {}
        out["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
        out["bytes_limit"] = mem.get("bytes_limit")
        recorded = consumed[-REPLAY_STEPS:]
        # the trainer dies: drop its device state before the resume loads
        # a second copy
        loop.params = loop.opt_state = None
        session.close()

        resumed = TrainSession.resume(store, NAMESPACE)
        if resumed.resume_step != entry.step:
            raise RuntimeError(f"resumed at step {resumed.resume_step}, "
                               f"checkpoint bound {entry.step}")
        state = resumed.restore_model(abstract_state(cfg))
        replay_loop = FusedTrainLoop(_fan_in(resumed, topo), step_fn,
                                     state["params"], state["opt"],
                                     topology=topo, depth=2)
        del state
        with replay_loop:
            replay = replay_loop.run(REPLAY_STEPS,
                                     on_batch=recorder(resumed.resume_step))
        replay_loop.params = replay_loop.opt_state = None
        resumed.close()

        losses = warm.losses + warm2.losses + timed.losses + after.losses
        out["losses"] = losses
        out["replay_losses"] = replay.losses
        if consumed[-REPLAY_STEPS:] != recorded:
            raise RuntimeError("replayed grids differ from the recorded ones")
        np.testing.assert_allclose(replay.losses, after.losses,
                                   rtol=REPLAY_LOSS_RTOL,
                                   err_msg="replayed losses")
        if not np.all(np.isfinite(losses + replay.losses)):
            raise RuntimeError(f"non-finite loss: {losses + replay.losses}")
        if abs(losses[0] - math.log(cfg.vocab_size)) > FIRST_LOSS_BOUND:
            raise RuntimeError(
                f"first loss {losses[0]:.4f} is more than "
                f"{FIRST_LOSS_BOUND} from ln V = "
                f"{math.log(cfg.vocab_size):.4f}")
        if window.lowerings:
            raise RuntimeError(f"{window.lowerings} compiles inside the "
                               f"timed steps")
        reference = sequential_read(store, topo,
                                    max(s for s, _ in consumed) + 1)
        bad = sorted({s for s, grid in consumed if grid != reference[s]})
        if bad:
            raise RuntimeError(f"consumed grids at steps {bad} differ from "
                               f"the sequential re-read")
        out["grids_checked"] = len(consumed)
    finally:
        stop.set()
        for t in producers:
            t.join(timeout=30.0)
    return out


# ---------------------------------------------------------------------------
# phase 4: the Pallas kernels
# ---------------------------------------------------------------------------

def _max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32)
                               - np.asarray(b, np.float32))))


def kernel_phase(shapes: KernelShapes) -> Dict[str, float]:
    """Each kernel once against its float32 oracle; returns the max abs
    error per kernel output and raises if any is out of tolerance."""
    interpret = use_interpret()
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    normal = lambda shape, dtype=bf16, scale=1.0: (
        jax.random.normal(next(keys), shape, jnp.float32) * scale
    ).astype(dtype)
    pairs = {}   # name -> (kernel output, oracle output, tolerance)

    B, S, H, G, dh = shapes.flash
    q, k, v = normal((B, S, H, dh)), normal((B, S, G, dh)), \
        normal((B, S, G, dh))
    out = flash_attention_fwd(q, k, v, causal=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        pairs["flash_attention"] = (out, flash_attention_ref(q, k, v),
                                    BF16_TOL)

    B, T, H, G, dh = shapes.decode
    q, kc, vc = normal((B, H, dh)), normal((B, T, G, dh)), \
        normal((B, T, G, dh))
    cur = T - T // 8
    out = decode_attention_fwd(q, kc, vc, cur, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        pairs["decode_attention"] = (out, decode_attention_ref(q, kc, vc, cur),
                                     BF16_TOL)

    x, w = normal(shapes.rmsnorm), normal(shapes.rmsnorm[-1:], jnp.float32)
    pairs["rmsnorm"] = (rmsnorm_fwd(x, w, interpret=interpret),
                        rmsnorm_ref(x, w), BF16_TOL)

    B, S, H, dh = shapes.wkv6
    r, k, v = (normal((B, S, H, dh), scale=0.5) for _ in range(3))
    decay = jnp.exp(-jnp.exp(normal((B, S, H, dh), jnp.float32, 0.5)))
    u = normal((H, dh), jnp.float32, 0.3)
    y, state = wkv6_fwd(r, k, v, decay, u, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        y_ref, state_ref = wkv6_ref(r, k, v, decay, u)
    pairs["wkv6.y"] = (y, y_ref, WKV6_Y_TOL)
    pairs["wkv6.state"] = (state, state_ref, WKV6_STATE_TOL)

    errors, failed = {}, []
    for name, (got, want, tol) in pairs.items():
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        errors[name] = _max_err(got, want)
        if not np.allclose(got, want, **tol):
            failed.append(name)
    if failed:
        raise RuntimeError(f"kernels out of tolerance: {failed} "
                           f"(max abs errors {errors})")
    return errors


# ---------------------------------------------------------------------------

def main() -> None:
    device = check_device()
    print(f"compile cache: {use_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    r = train_phase(CHIP)
    m = CHIP.model
    print(f"train: {m.name} d_model={m.d_model} heads={m.num_heads}/"
          f"{m.num_kv_heads} d_ff={m.d_ff} layers={m.num_layers} "
          f"vocab={m.vocab_size} GB={CHIP.topology.global_batch} "
          f"S={CHIP.topology.seq_len} microbatches={CHIP.microbatches}",
          flush=True)
    print(f"train: first step compile {r['first_step_compile_s']:.3f}s "
          f"(persistent cache hits {r['first_step_cache_hits']}), "
          f"wall {r['first_step_wall_s']:.3f}s", flush=True)
    print(f"train: median step {r['median_step_s']:.6f}s, "
          f"{r['tokens_per_s']:.1f} tokens/s over {TIMED_STEPS} steps; "
          f"compiles inside them: {r['compiles_in_timed_steps']}", flush=True)
    print(f"train: stall fractions {r['stall_fractions']}", flush=True)
    print(f"train: peak_bytes_in_use {r['peak_bytes_in_use']} of "
          f"bytes_limit {r['bytes_limit']}", flush=True)
    print(f"train: losses {r['losses']}; replay {r['replay_losses']}; "
          f"{r['grids_checked']} grids equal the sequential re-read "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)

    errors = kernel_phase(CHIP_KERNELS)
    print(f"kernels: max abs error vs ref {errors}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu writes no logs
    main()
