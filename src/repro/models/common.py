"""Shared model substrate: parameter specs, init, norms, RoPE, attention.

Conventions
-----------
* Parameters are nested dicts of arrays. Every leaf is declared first as a
  ``ParamSpec`` (shape + logical axes + init), from which both real
  initialization (smoke tests, examples) and abstract ShapeDtypeStructs +
  NamedShardings (512-device dry-run) derive — full configs are never
  materialized.
* Layer stacks are scanned: per-layer params carry a leading "layers" axis.
* Compute runs in ``cfg.compute_dtype`` (bf16); params stored in
  ``cfg.param_dtype``.
* Logical axes (mapped to mesh axes in repro.sharding.specs):
    "layers"  — scan dim, never sharded
    "embed"   — d_model dims of weights  -> FSDP ("data"[, "pod"])
    "heads"   — attention q-head dim     -> TP ("model") when divisible
    "kv"      — kv-head dim              -> TP when divisible else replicated
    "qkv"     — merged head*dh output    -> TP
    "mlp"     — d_ff dim                 -> TP
    "vocab"   — vocabulary dim           -> TP
    "experts" — MoE expert dim           -> EP ("model")
    None      — replicated
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.float32
    init: str = "normal"        # normal | zeros | ones | embed
    init_scale: float = 1.0     # multiplies the fan-in init stddev

    def __post_init__(self):
        if len(self.shape) != len(self.logical_axes):
            raise ValueError(f"shape {self.shape} vs axes {self.logical_axes}")


def is_param_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_tree_map(fn: Callable[[ParamSpec], Any], specs) -> Any:
    return jax.tree_util.tree_map(fn, specs, is_leaf=is_param_spec)


def init_param(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape, jnp.float32)
                * spec.init_scale).astype(spec.dtype)
    # fan-in scaled normal for weight matrices; fan-in = product of all dims
    # except the last (output) dim, per non-layer axes.
    shape = spec.shape
    # drop the scan ("layers") dim from fan computation
    dims = [s for s, a in zip(shape, spec.logical_axes) if a != "layers"]
    fan_in = int(np.prod(dims[:-1])) if len(dims) > 1 else max(1, dims[0])
    std = spec.init_scale / np.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(spec.dtype)


def init_params(specs, seed: int = 0):
    """Materialize a ParamSpec tree (small/smoke configs only)."""
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_param_spec)
    keys = jax.random.split(jax.random.PRNGKey(seed), max(1, len(leaves)))
    vals = [init_param(s, k) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(specs):
    """ShapeDtypeStruct tree for AOT lowering (dry-run)."""
    return spec_tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def cast(x, dtype):
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if isinstance(a, jax.Array) or hasattr(a, "astype") else a, x)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in fp32 accumulation, output in x.dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 publishes it:
    ``rope_scaling`` of type ``yarn`` in its config.json."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, yarn: YarnScaling) -> np.ndarray:
    """Per-pair inverse frequencies: the original ones for pairs that turn
    more than ``beta_fast`` times over the original context, those divided by
    ``factor`` for pairs that turn fewer than ``beta_slow`` times, and a
    linear ramp between (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    half = head_dim // 2
    extra = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / head_dim)
    inter = extra / yarn.factor

    def dim_of(rotations):
        return head_dim * np.log(yarn.original_max_position
                                 / (rotations * 2 * np.pi)) \
            / (2 * np.log(theta))

    low = max(int(np.floor(dim_of(yarn.beta_fast))), 0)
    high = min(int(np.ceil(dim_of(yarn.beta_slow))), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                yarn: Optional[YarnScaling] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int -> (cos, sin) of shape (..., head_dim//2).
    With ``yarn``, YaRN's frequencies, and cos and sin scaled by
    mscale(mscale) / mscale(mscale_all_dim), which is 1 where the two agree."""
    half = head_dim // 2
    if yarn is None:
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, yarn))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) \
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., n_heads, head_dim); cos/sin broadcastable to (..., 1, head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token CE. logits (..., V) possibly vocab-sharded; labels (...) int.

    Uses one-hot einsum for the label logit (collective-friendly when V is
    sharded) and fp32 logsumexp.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    label_logit = jnp.sum(lf * onehot, axis=-1)
    nll = lse - label_logit
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# Attention (GQA) — dense, chunked (XLA-flash), and decode paths
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def repeat_kv(x: jax.Array, n_heads: int) -> jax.Array:
    """(B, T, G, dh) -> (B, T, H, dh) by repeating each KV head H//G times.

    Deliberately a repeat, NOT a (G, rep) reshape of the q heads: reshaping a
    TP-sharded head dim breaks GSPMD propagation, while repeating a replicated
    KV tensor onto a sharded head dim is a local slice on every device.
    """
    G = x.shape[2]
    if G == n_heads:
        return x
    return jnp.repeat(x, n_heads // G, axis=2)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, q_offset: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """Reference attention; materializes (S, T) scores. Use for short seq.
    q, k: (B, S|T, H|G, dh); v: (B, T, G, dv), dv may differ from dh; the
    scores are scaled by ``scale`` (default 1/sqrt(dh))."""
    B, S, H, dh = q.shape
    T = k.shape[1]
    kh = repeat_kv(k, H)
    vh = repeat_kv(v, H)
    scores = jnp.einsum("bshd,bthd->bhst", q, kh).astype(jnp.float32)
    scores *= 1.0 / np.sqrt(dh) if scale is None else scale
    if causal:
        qpos = jnp.arange(S)[:, None] + q_offset
        kpos = jnp.arange(T)[None, :]
        mask = qpos >= kpos
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, vh)
    return out


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, chunk: int = 1024,
                      q_offset: int = 0,
                      scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention scanning KV chunks: O(S * chunk) score memory.

    This is the TPU-native 'flash' adaptation expressible in pure XLA (the
    Pallas kernel in repro.kernels.flash_attention is the tuned version); it is
    the default for long sequences so prefill_32k fits without S^2 temps.
    """
    B, S, H, dh = q.shape
    T, dv = k.shape[1], v.shape[-1]
    kh = repeat_kv(k, H)
    vh = repeat_kv(v, H)
    if T % chunk:
        # pad KV to a chunk multiple; padded keys are masked out
        pad = chunk - T % chunk
        kh = jnp.pad(kh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = kh.shape[1] // chunk
    qs = (q * (1.0 / np.sqrt(dh) if scale is None else scale)).astype(q.dtype)
    kc = kh.reshape(B, n_chunks, chunk, H, dh).transpose(1, 0, 2, 3, 4)
    vc = vh.reshape(B, n_chunks, chunk, H, dv).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(S) + q_offset

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, ci = inputs
        kpos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bshd,bthd->bhst", qs, kb).astype(jnp.float32)
        valid = kpos[None, :] < T  # in-range (pre-pad) keys
        if causal:
            valid = valid & (qpos[:, None] >= kpos[None, :])
        s = jnp.where(valid[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale_old = jnp.exp(m - m_new)
        l_new = l * scale_old + jnp.sum(p, axis=-1)
        acc_new = acc * scale_old[..., None] + jnp.einsum(
            "bhst,bthd->bhsd", p.astype(vb.dtype), vb).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = jnp.zeros((B, H, S, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kc, vc, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention(q, k, v, causal=True, impl="auto", chunk=1024, q_offset=0,
              scale=None):
    """Causal attention of q (B, S, H, dh) over k (B, T, G, dh) and v
    (B, T, G, dv); ``scale`` multiplies the scores (default 1/sqrt(dh))."""
    if impl == "auto":
        impl = "chunked" if k.shape[1] > 4096 else "dense"
    if impl == "dense":
        return dense_attention(q, k, v, causal=causal, q_offset=q_offset,
                               scale=scale)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, chunk=chunk,
                                 q_offset=q_offset, scale=scale)
    if impl == "pallas":
        if scale is not None or v.shape[-1] != q.shape[-1]:
            raise ValueError("the flash kernel takes neither a softmax "
                             "scale nor a v width other than q's")
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_index: jax.Array) -> jax.Array:
    """Single-token attention against a (possibly sequence-sharded) KV cache.

    q: (B, H, dh); caches: (B, T, G, dh); cur_index: scalar int (tokens valid
    in [0, cur_index]). Reductions over T lower to all-reduces when T is
    sharded — the XLA analogue of flash-decode.
    """
    B, H, dh = q.shape
    kh = repeat_kv(k_cache, H)
    vh = repeat_kv(v_cache, H)
    # Keep the repeated KV sequence-sharded: without these constraints GSPMD
    # re-shards the (B, T, H, dh) broadcast onto q's head sharding, which
    # requires an "involuntary full rematerialization" — a ~1 GiB all-gather of
    # the cache per layer per token (measured). Gathering q (a few MB over
    # heads) is the right side of that trade — this is flash-decode in XLA.
    kh = with_logical_constraint(kh, ("batch", "cache_seq", None, None))
    vh = with_logical_constraint(vh, ("batch", "cache_seq", None, None))
    qs = (q * (1.0 / np.sqrt(dh))).astype(q.dtype)
    s = jnp.einsum("bhd,bthd->bht", qs, kh).astype(jnp.float32)
    s = with_logical_constraint(s, ("batch", None, "cache_seq"))
    T = k_cache.shape[1]
    valid = jnp.arange(T)[None, None, :] <= cur_index
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bthd->bhd", p.astype(vh.dtype), vh)
    return out


def decode_attention_readonly(q: jax.Array, k_cache: jax.Array,
                              v_cache: jax.Array, k_new: jax.Array,
                              v_new: jax.Array, pos: jax.Array) -> jax.Array:
    """Decode attention against a STALE cache (positions < pos) plus the
    current token's (k_new, v_new) combined analytically — lets the cache stay
    read-only inside the layer scan (no double-buffering; see decode_step).

    q/k_new/v_new: (B, H|G, dh); caches: (B, T, G, dh).
    """
    B, H, dh = q.shape
    kh = repeat_kv(k_cache, H)
    vh = repeat_kv(v_cache, H)
    kh = with_logical_constraint(kh, ("batch", "cache_seq", None, None))
    vh = with_logical_constraint(vh, ("batch", "cache_seq", None, None))
    knh = repeat_kv(k_new[:, None], H)[:, 0]           # (B, H, dh)
    vnh = repeat_kv(v_new[:, None], H)[:, 0]
    qs = (q * (1.0 / np.sqrt(dh))).astype(q.dtype)
    s = jnp.einsum("bhd,bthd->bht", qs, kh).astype(jnp.float32)
    s = with_logical_constraint(s, ("batch", None, "cache_seq"))
    T = k_cache.shape[1]
    valid = jnp.arange(T)[None, None, :] < pos          # strictly past
    s = jnp.where(valid, s, NEG_INF)
    s_new = jnp.einsum("bhd,bhd->bh", qs, knh).astype(jnp.float32)
    m = jnp.maximum(jnp.max(s, axis=-1), s_new)
    p = jnp.exp(s - m[..., None])
    p_new = jnp.exp(s_new - m)
    denom = jnp.sum(p, axis=-1) + p_new
    out = jnp.einsum("bht,bthd->bhd", p.astype(vh.dtype), vh)
    out = out + p_new[..., None].astype(vnh.dtype) * vnh
    return out / denom[..., None].astype(out.dtype)


def cache_update(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new`` (B, 1, G, dh) into ``cache`` (B, T, G, dh) at seq position
    ``pos`` via a one-hot masked select.

    Deliberately NOT dynamic_update_slice: a DUS at a runtime offset on a
    sequence-sharded dim forces GSPMD to all-gather the cache (measured: ~74 GB
    per decode step for granite-8b). The masked select is purely elementwise,
    so every device touches only its local shard; the residual cost (local
    cache rewrite) is a further Pallas/shard_map hillclimb noted in
    EXPERIMENTS.md §Perf.
    """
    T = cache.shape[1]
    hit = (jnp.arange(T) == pos)[None, :, None, None]
    return jnp.where(hit, new.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def swiglu(x_gate: jax.Array, x_up: jax.Array) -> jax.Array:
    return jax.nn.silu(x_gate) * x_up


def shift_tokens_right(x: jax.Array) -> jax.Array:
    """(B, S) -> input/label split helper: labels are x shifted left."""
    return x


def with_logical_constraint(x, logical_axes, rules=None):
    """Apply a sharding constraint if a mesh context + rules are active."""
    if rules is None:
        from repro.sharding.specs import current_rules
        rules = current_rules()
    if rules is None:
        return x
    return rules.constrain(x, logical_axes)
