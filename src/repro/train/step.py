"""Jittable step functions: train (microbatched grad accumulation), prefill,
decode — plus the abstract input specs used by the multi-pod dry-run.

``make_train_step`` builds a donatable (state, batch) -> (state, metrics) step:

  * batch (GB, S) is reshaped to (n_micro, GB/n_micro, S) and scanned, grads
    accumulated in fp32 — per-microbatch activation memory is what remat +
    microbatching bound on a 16 GB chip;
  * the AdamW update runs once on the accumulated grads;
  * an expert model's counts (``COUNTS``) ride in the metrics, summed or
    maxed over the microbatches.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import ModelConfig
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   init_opt_state)


#: the model's counts that the step's metrics carry, each folded over the
#: microbatches: the choices computed here, and the fullest held expert
COUNTS = {"moe_rows_local": jnp.add, "moe_rows_max": jnp.maximum}


@dataclass(frozen=True)
class StepConfig:
    microbatches: int = 1
    frontend_prefix: int = 0   # P positions of precomputed embeddings
    # Gradient accumulation dtype across microbatches. float32 is the faithful
    # default; bfloat16 halves both the accumulator HBM and the cross-data
    # grad-reduction payload (the largest collective in llama3-405b train —
    # measured 27%); an accuracy trade recorded in §Perf C5.
    grad_accum_dtype: str = "float32"


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; the dry-run's only "data")
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, global_batch: int, seq_len: int,
                kind: str, frontend_prefix: int = 0) -> Dict[str, Any]:
    """Abstract model inputs for (arch x shape). Returns {name: (SDS, axes)}."""
    B, S = global_batch, seq_len
    out: Dict[str, Any] = {}
    tok_shape = (B, S, cfg.num_codebooks) if cfg.family == "audio" else (B, S)
    if kind == "decode":
        tok_shape = (B, cfg.num_codebooks) if cfg.family == "audio" else (B,)
    out["tokens"] = (jax.ShapeDtypeStruct(tok_shape, jnp.int32),
                     ("batch",) + (None,) * (len(tok_shape) - 1))
    if cfg.frontend != "none" and kind != "decode":
        P = frontend_prefix or max(16, min(256, S // 8))
        out["frontend_embeds"] = (
            jax.ShapeDtypeStruct((B, P, cfg.d_model), jnp.float32),
            ("batch", None, None))
    return out


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                    step_cfg: StepConfig = StepConfig(),
                    param_spec_tree=None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    ``param_spec_tree`` (ParamSpec tree) lets the microbatch grad accumulator
    carry explicit sharding constraints: without them XLA keeps the carry
    under-sharded and ALL-REDUCES each microbatch's full per-layer weight
    grads over the data axes instead of REDUCE-SCATTERING into the FSDP layout
    (measured: 27% of llama3-405b train collective bytes; §Perf C4).
    """

    n_micro = step_cfg.microbatches

    def _constrain_grads(grads):
        if param_spec_tree is None:
            return grads
        from repro.models.common import with_logical_constraint
        import jax.tree_util as jtu
        flat_g, treedef = jtu.tree_flatten(grads)
        flat_s = jtu.tree_leaves(param_spec_tree,
                                 is_leaf=lambda x: hasattr(x, "logical_axes"))
        return jtu.tree_unflatten(treedef, [
            with_logical_constraint(g, s.logical_axes)
            for g, s in zip(flat_g, flat_s)])

    def loss_for(params, mb):
        loss, metrics = M.loss_fn(cfg, params, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def train_step(params, opt_state, batch):
        tokens = batch["tokens"]
        GB = tokens.shape[0]
        assert GB % n_micro == 0, (GB, n_micro)
        mb_sz = GB // n_micro

        def reshape_mb(x):
            return x.reshape((n_micro, mb_sz) + x.shape[1:])

        micro = {k: reshape_mb(v) for k, v in batch.items()}

        acc_dt = jnp.dtype(step_cfg.grad_accum_dtype)
        zero_grads = _constrain_grads(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dt), params))

        def micro_body(acc, mb):
            g_acc, loss_acc, aux_acc, counts_acc = acc
            (loss, metrics), grads = grad_fn(params, mb)
            grads = _constrain_grads(grads)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dt), g_acc, grads)
            g_acc = _constrain_grads(g_acc)
            counts = {k: COUNTS[k](counts_acc[k], metrics[k])
                      for k in counts_acc}
            return (g_acc, loss_acc + loss, aux_acc + metrics["aux_loss"],
                    counts), None

        if n_micro > 1:
            counts0 = {k: jnp.zeros((), jnp.int32) for k in COUNTS} \
                if cfg.family == "moe" else {}
            (grads, loss_sum, aux_sum, counts), _ = jax.lax.scan(
                micro_body, (zero_grads, 0.0, 0.0, counts0), micro)
        else:
            mb0 = {k: v[0] for k, v in micro.items()}
            (loss, metrics), grads = grad_fn(params, mb0)
            grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32),
                                           grads)
            loss_sum, aux_sum = loss, metrics["aux_loss"]
            counts = {k: metrics[k] for k in COUNTS if k in metrics}

        grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
        new_params, new_opt, om = adamw_update(opt_cfg, params, grads,
                                               opt_state)
        metrics = {"loss": loss_sum / n_micro, "aux_loss": aux_sum / n_micro,
                   **om, **counts}
        return new_params, new_opt, metrics

    return train_step


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        return M.prefill(cfg, params, batch)
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, state, tokens, pos):
        return M.decode_step(cfg, params, state, tokens, pos)
    return decode_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, metrics = M.loss_fn(cfg, params, batch)
        return loss, metrics
    return eval_step
