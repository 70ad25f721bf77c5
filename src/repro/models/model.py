"""Family dispatch: one API over all assigned architectures.

  param_specs(cfg)                         -> ParamSpec tree
  forward(cfg, params, batch)              -> (logits, aux)
  loss_fn(cfg, params, batch)              -> (loss, metrics); an expert
                                              model's metrics add its counts
  decode_state_specs / decode_step         -> serving (KV cache or recurrent)
  prefill                                  -> attention families only

``batch`` is a dict: tokens (B, S) int32 ((B, S, K) audio), optional
frontend_embeds (B, P, d_model) for vlm/audio stubs. Labels are next-token
shifted in-loss; frontend prefix positions are masked out.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import mamba2, rwkv6, transformer
from repro.models.common import softmax_cross_entropy
from repro.models.config import ModelConfig

_ATTN_FAMILIES = ("dense", "moe", "vlm", "audio")


def param_specs(cfg: ModelConfig):
    if cfg.family in _ATTN_FAMILIES:
        return transformer.param_specs(cfg)
    if cfg.family == "rwkv":
        return rwkv6.param_specs(cfg)
    if cfg.family == "hybrid":
        return mamba2.param_specs(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")


def _forward(cfg: ModelConfig, params, batch: Dict):
    """(logits, aux, counts): ``counts`` as ``transformer.forward`` gives
    them, empty for the families without experts."""
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    if cfg.family in _ATTN_FAMILIES:
        return transformer.forward(cfg, params, tokens, frontend_embeds=fe)
    if cfg.family == "rwkv":
        return (*rwkv6.forward(cfg, params, tokens), {})
    if cfg.family == "hybrid":
        return (*mamba2.forward(cfg, params, tokens), {})
    raise ValueError(cfg.family)


def forward(cfg: ModelConfig, params, batch: Dict) -> Tuple[jax.Array, jax.Array]:
    logits, aux, _ = _forward(cfg, params, batch)
    return logits, aux


def loss_fn(cfg: ModelConfig, params, batch: Dict) -> Tuple[jax.Array, Dict]:
    logits, aux, counts = _forward(cfg, params, batch)
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    P = fe.shape[1] if fe is not None else 0
    if cfg.family == "audio":
        # logits: (B, S+P?, K, V); audio has no frontend prefix in logits mask
        # handling below (frontend enters as conditioning prefix).
        tok_logits = logits[:, P:][:, :-1]
        labels = tokens[:, 1:]
        B, Sm1, K, V = tok_logits.shape
        loss = softmax_cross_entropy(
            tok_logits.reshape(B, Sm1 * K, V),
            labels.reshape(B, Sm1 * K))
    else:
        tok_logits = logits[:, P:][:, :-1]
        labels = tokens[:, 1:]
        loss = softmax_cross_entropy(tok_logits, labels)
    total = loss + aux
    return total, {"ce_loss": loss, "aux_loss": aux, **counts}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def decode_state_specs(cfg: ModelConfig, batch: int, max_seq: int):
    """(ShapeDtypeStruct, logical_axes) dict for the decode-time state."""
    if cfg.family in _ATTN_FAMILIES:
        return transformer.init_cache_specs(cfg, batch, max_seq)
    if cfg.family == "rwkv":
        return rwkv6.init_state_specs(cfg, batch)
    if cfg.family == "hybrid":
        return mamba2.init_state_specs(cfg, batch, max_seq)
    raise ValueError(cfg.family)


def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int):
    return {k: jnp.zeros(s.shape, s.dtype)
            for k, (s, _a) in decode_state_specs(cfg, batch, max_seq).items()}


def decode_step(cfg: ModelConfig, params, state, tokens, pos):
    if cfg.family in _ATTN_FAMILIES:
        return transformer.decode_step(cfg, params, state, tokens, pos)
    if cfg.family == "rwkv":
        return rwkv6.decode_step(cfg, params, state, tokens, pos)
    if cfg.family == "hybrid":
        return mamba2.decode_step(cfg, params, state, tokens, pos)
    raise ValueError(cfg.family)


def prefill(cfg: ModelConfig, params, batch: Dict):
    """Prefill: last-position logits + the serving state (KV cache for
    attention families; recurrent conv/SSD/WKV state for SSM/hybrid)."""
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    if cfg.family in _ATTN_FAMILIES:
        return transformer.prefill(cfg, params, tokens, frontend_embeds=fe)
    if cfg.family == "rwkv":
        return rwkv6.prefill(cfg, params, tokens)
    if cfg.family == "hybrid":
        return mamba2.prefill(cfg, params, tokens)
    raise ValueError(cfg.family)
