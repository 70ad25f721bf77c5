"""Fig. 17 — fused training loop: end-to-end tokens/s, colocated vs mq vs tgb.

The tentpole measurement for the paper's compute-bound claim: a real jitted
train step (``train/step.py`` over ``models/`` + Pallas-lowerable kernels)
driven by ``FusedTrainLoop`` off each data-plane backend, at staging-ring
depths {0, 2, 4}:

  * ``colocated`` — the in-rank baseline: the worker pool feeds sample
    indices through ``PackingTokenSource`` (tokenize+pack on the staging
    thread, queue contention modeled by ``ColocatedPipeline``);
  * ``mq``       — the strict-TGB Kafka baseline: whole-message fetch with
    local slicing (the D x C read amplification);
  * ``tgb``      — the object-store-native plane: per-rank range reads
    against the simulated S3-class latency model, consumer prefetch +
    the loop's device staging ring.

``depth=0`` is the synchronous strawman (fetch + h2d on the critical path
every step); ``depth>=2`` overlaps fetch/pack/h2d of batch N+1 with the
step on batch N. Derived columns per arm: ``tokens_per_s`` plus the
stall-attribution split (data_wait/h2d/compute fractions of step wall
clock).

``us_per_call`` is mean step wall-clock µs. ``check_fig17.py`` gates: tgb
at depth >= 2 stays within 10% of colocated tokens/s with data-wait
fraction < 15%, and beats its own depth-0 arm.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from benchmarks.common import Row, bench_broker, bench_store
from repro.configs.registry import get_smoke_config
from repro.data.colocated import ColocatedConfig
from repro.dataplane import Topology, open_dataplane
from repro.models import init_params, param_specs
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.pipeline import (FusedTrainLoop, FusedReport,
                                  PackingTokenSource, ReaderFanInSource)
from repro.train.step import StepConfig, make_train_step

DP, CP = 2, 1
GB, SEQ = 4, 128
TOPO = Topology(dp=DP, cp=CP, global_batch=GB, seq_len=SEQ)
DEPTHS = (0, 2, 4)
BACKENDS = ("colocated", "mq", "tgb")
NS = "runs/fig17"
WARMUP_STEPS = 2
#: per-sample preprocessing cost for the colocated baseline: light, so the
#: baseline is near its expert-tuned best (the gap fig17 measures is the
#: transport, not a handicapped strawman)
COLOC_COST_S = 0.0002

#: fig17 model families: one representative architecture per sequence-mixing
#: class, so the fused-loop stall split is validated beyond the transformer
#: path (attention, SSM, linear-attention RNN, sparse MoE have very different
#: compute shapes per token — the data plane must hide the fetch under all
#: of them). Each is the dense smoke config at a (GB, SEQ) where one CPU
#: step is a few tens of ms of real compute — comparable to one S3-class
#: fetch, so the synchronous depth-0 arm visibly stalls while a
#: well-overlapped ring hides the same fetch entirely.
FAMILIES = {
    "transformer": "granite_8b",
    "mamba2": "zamba2_7b",
    "rwkv6": "rwkv6_3b",
    "moe": "deepseek_moe_16b",
}
DEFAULT_FAMILY = "transformer"


def _model_for(family: str):
    if family not in FAMILIES:
        raise ValueError(f"unknown model family {family!r}; "
                         f"choose from {sorted(FAMILIES)}")
    return get_smoke_config(FAMILIES[family]).replace(
        name=f"fig17-{family}", vocab_size=512)


#: module-level so the token-stream helpers see the active family's vocab;
#: ``run()`` swaps it per invocation (the harness default stays transformer,
#: which keeps the gated fig17/{backend}/d{depth} row names unchanged)
MODEL = _model_for(DEFAULT_FAMILY)


def _tokens(n: int, base: int = 0) -> np.ndarray:
    """Deterministic token stream (same bytes for every backend)."""
    return ((np.arange(base, base + n) * 7 + 3)
            % MODEL.vocab_size).astype(np.int32)


def _sample_tokens(indices: np.ndarray) -> np.ndarray:
    """Colocated arm: sample index -> its SEQ-token slice of the stream."""
    offs = indices.astype(np.int64)[:, None] * SEQ + np.arange(SEQ)[None, :]
    return ((offs.ravel() * 7 + 3) % MODEL.vocab_size).astype(np.int32)


class _Arms:
    """Shared trainer state: one jitted step, one param init, reused so
    every arm measures the identical compute."""

    def __init__(self):
        import jax
        self.step_fn = jax.jit(make_train_step(
            MODEL, OptimizerConfig(), StepConfig()))
        self.params = init_params(param_specs(MODEL), seed=0)
        self.opt = init_opt_state(self.params)

    def drive(self, source, depth: int, steps: int) -> FusedReport:
        loop = FusedTrainLoop(source, self.step_fn, self.params, self.opt,
                              topology=TOPO, depth=depth, timeout_s=60.0,
                              instance=f"fig17-d{depth}")
        with loop:
            loop.run(WARMUP_STEPS)        # jit compile + ring fill
            return loop.run(steps)


def _source_tgb(store, depth: int) -> ReaderFanInSource:
    sess = open_dataplane(store, TOPO, backend="tgb", namespace=NS)
    readers = [sess.reader(dp_rank=d, cp_rank=c,
                           prefetch_depth=max(4, 2 * depth))
               for d in range(DP) for c in range(CP)]
    return ReaderFanInSource(readers, TOPO)


def _source_mq(broker, depth: int) -> ReaderFanInSource:
    sess = open_dataplane(broker, TOPO, backend="mq", namespace=NS)
    readers = [sess.reader(dp_rank=d, cp_rank=c)
               for d in range(DP) for c in range(CP)]
    return ReaderFanInSource(readers, TOPO)


def _source_colocated(depth: int) -> PackingTokenSource:
    sess = open_dataplane(None, TOPO, backend="colocated", namespace=NS,
                          config=ColocatedConfig(),
                          preprocess_cost_s=lambda i: COLOC_COST_S,
                          batch_cpu_items=GB)
    writer = sess.writer()
    writer.__enter__()                    # start the worker pool
    reader = sess.reader()

    def pull(timeout_s: Optional[float]) -> Optional[np.ndarray]:
        indices = np.frombuffer(
            reader.next_batch(timeout_s=timeout_s).payload, dtype=np.int32)
        return _sample_tokens(indices)

    src = PackingTokenSource(pull, TOPO)
    src._coloc_writer = writer            # keep the pool alive with the arm
    return src


def run(quick: bool = True,
        model_family: str = DEFAULT_FAMILY) -> List[Row]:
    global MODEL
    MODEL = _model_for(model_family)
    # non-default families get their own row prefix so the CI gate (which
    # keys on the transformer rows) and a manual sweep can coexist in one CSV
    prefix = ("fig17" if model_family == DEFAULT_FAMILY
              else f"fig17/{model_family}")
    steps = 12 if quick else 24
    n_batches = WARMUP_STEPS + steps + max(DEPTHS) + 4
    stream = _tokens(n_batches * GB * SEQ)

    arms = _Arms()

    # produce once per transport; every depth arm replays from step 0
    tgb_store = bench_store()
    with open_dataplane(tgb_store, TOPO, backend="tgb",
                        namespace=NS).writer("w0") as w:
        w.write_tokens(stream)
    mq_broker = bench_broker()
    with open_dataplane(mq_broker, TOPO, backend="mq",
                        namespace=NS).writer("w0") as w:
        w.write_tokens(stream)

    rows: List[Row] = []
    reports: Dict[tuple, FusedReport] = {}
    # depth-major order: the gate compares backends at equal depth, and
    # running those arms back-to-back keeps slow machine drift (CPU
    # frequency, XLA thread-pool state) out of the comparison
    for depth in DEPTHS:
        for backend in BACKENDS:
            if backend == "tgb":
                src = _source_tgb(tgb_store, depth)
            elif backend == "mq":
                src = _source_mq(mq_broker, depth)
            else:
                src = _source_colocated(depth)
            try:
                rep = arms.drive(src, depth, steps)
            finally:
                w = getattr(src, "_coloc_writer", None)
                if w is not None:
                    w.__exit__(None, None, None)
            reports[(backend, depth)] = rep
            attr = rep.attribution()
            # median step wall, not mean: a single scheduler straggler in a
            # 10-step window would otherwise dominate the arm comparison
            med_step_s = float(np.median([t.wall_s for t in rep.timings]))
            rows.append(Row(
                f"{prefix}/{backend}/d{depth}", med_step_s * 1e6,
                f"tokens_per_s={GB * SEQ / med_step_s:.0f};"
                f"data_wait_frac={attr['data_wait']:.3f};"
                f"h2d_frac={attr['h2d']:.3f};"
                f"compute_frac={attr['compute']:.3f};"
                f"bound={attr['bound']};"
                f"steps={steps}"))
    rows.sort(key=lambda r: r.name)
    return rows


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="fig17 fused train loop, one model family per run")
    ap.add_argument("--model-family", default=DEFAULT_FAMILY,
                    choices=sorted(FAMILIES),
                    help="sequence-mixing architecture for the train step "
                         "(default: %(default)s)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(quick=not args.full, model_family=args.model_family):
        print(row.csv(), flush=True)


if __name__ == "__main__":
    main()
