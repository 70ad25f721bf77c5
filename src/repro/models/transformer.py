"""Decoder-only transformer (GQA or latent attention, SwiGLU or experts),
scanned over layers.

Covers families: dense, moe (FFN swapped for repro.models.moe), vlm and audio
(backbone identical; modality frontends enter as precomputed embeddings).

The stack is one run of equal layers per kind, each scanned on its own with
the same remat policy: ``dense_layers`` (``cfg.first_dense_layers`` leading
dense layers of an MoE model, d_ff wide), then ``layers``.

Weights keep explicit head axes — (D, H, dh) etc. — so TP sharding of the head
dim never crosses head boundaries; when H is not divisible by the TP size the
sharding rules fall back to sequence-parallel attention activations.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import (ParamSpec, apply_rope, attention,
                                 cache_update, decode_attention,
                                 decode_attention_readonly, rms_norm,
                                 rope_angles, swiglu, with_logical_constraint,
                                 yarn_mscale)
from repro.models.config import ModelConfig
from repro.models.moe import moe_ffn, moe_param_specs


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _mla_param_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((L, D, H, dn + dr), ("layers", "embed", "heads", None)),
        "wkv_a": ParamSpec((L, D, r + dr), ("layers", "embed", None)),
        "kv_norm": ParamSpec((L, r), ("layers", None), init="ones"),
        "wkv_b": ParamSpec((L, r, H, dn + dv), ("layers", None, "heads", None)),
        "wo": ParamSpec((L, H, dv, D), ("layers", "heads", None, "embed")),
    }


def layer_param_specs(cfg: ModelConfig, L: Optional[int] = None,
                      moe: Optional[bool] = None) -> Dict[str, ParamSpec]:
    """Specs for a stack of L transformer layers (leading 'layers' axis);
    ``moe`` says whether their FFN is the expert layer (default: the
    family's)."""
    if L is None:
        L = cfg.num_layers
    if moe is None:
        moe = cfg.family == "moe"
    D, H, G, dh, F = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                      cfg.head_dim, cfg.d_ff)
    specs: Dict[str, ParamSpec] = {
        "attn_norm": ParamSpec((L, D), ("layers", "embed"), init="ones"),
        "mlp_norm": ParamSpec((L, D), ("layers", "embed"), init="ones"),
    }
    if cfg.mla:
        specs.update(_mla_param_specs(cfg, L))
    else:
        specs.update({
            "wq": ParamSpec((L, D, H, dh), ("layers", "embed", "heads", None)),
            "wk": ParamSpec((L, D, G, dh), ("layers", "embed", "kv", None)),
            "wv": ParamSpec((L, D, G, dh), ("layers", "embed", "kv", None)),
            "wo": ParamSpec((L, H, dh, D), ("layers", "heads", None, "embed")),
        })
    if cfg.qkv_bias:
        specs.update({
            "bq": ParamSpec((L, H, dh), ("layers", "heads", None), init="zeros"),
            "bk": ParamSpec((L, G, dh), ("layers", "kv", None), init="zeros"),
            "bv": ParamSpec((L, G, dh), ("layers", "kv", None), init="zeros"),
        })
    if moe:
        specs.update(moe_param_specs(cfg, L))
    else:
        specs.update({
            "w_gate": ParamSpec((L, D, F), ("layers", "embed", "mlp")),
            "w_up": ParamSpec((L, D, F), ("layers", "embed", "mlp")),
            "w_down": ParamSpec((L, F, D), ("layers", "mlp", "embed")),
        })
    return specs


def param_specs(cfg: ModelConfig) -> Dict:
    D, V = cfg.d_model, cfg.vocab_size
    if cfg.family == "audio":
        embed = ParamSpec((cfg.num_codebooks, V, D), (None, "vocab", "embed"),
                          init="embed", init_scale=0.02)
        unembed = ParamSpec((cfg.num_codebooks, D, V), (None, "embed", "vocab"))
    else:
        embed = ParamSpec((V, D), ("vocab", "embed"), init="embed",
                          init_scale=0.02)
        unembed = ParamSpec((D, V), ("embed", "vocab"))
    n_dense = cfg.first_dense_layers
    specs = {
        "embed": embed,
        "layers": layer_param_specs(cfg, cfg.num_layers - n_dense),
        "final_norm": ParamSpec((D,), ("embed",), init="ones"),
    }
    if n_dense:
        specs["dense_layers"] = layer_param_specs(cfg, n_dense, moe=False)
    if not cfg.tie_embeddings:
        specs["unembed"] = unembed
    return specs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _embed_tokens(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    cd = cfg.cdtype
    if cfg.family == "audio":
        # tokens: (B, S, K); sum the K codebook embeddings
        parts = [jnp.take(params["embed"][k], tokens[..., k], axis=0)
                 for k in range(cfg.num_codebooks)]
        return sum(parts).astype(cd)
    return jnp.take(params["embed"], tokens, axis=0).astype(cd)


def _unembed(cfg: ModelConfig, params, h: jax.Array) -> jax.Array:
    cd = cfg.cdtype
    if cfg.family == "audio":
        return jnp.einsum("bsd,kdv->bskv", h, params["unembed"].astype(cd))
    table = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    logits = jnp.einsum("bsd,dv->bsv", h, table.astype(cd))
    if cfg.logits_softcap:
        c = cfg.logits_softcap
        logits = jnp.tanh(logits / c) * c
    return logits


def _qkv(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
         cos: jax.Array, sin: jax.Array):
    """GQA projections of the normed x, q and k rotated."""
    cd = cfg.cdtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
    k = jnp.einsum("bsd,dgk->bsgk", x, p["wk"].astype(cd))
    v = jnp.einsum("bsd,dgk->bsgk", x, p["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def attn_block(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
               cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, D) normed input -> attention output (B, S, D)."""
    cd = cfg.cdtype
    q, k, v = _qkv(cfg, p, x, cos, sin)
    q = with_logical_constraint(q, ("batch", "seq_sp", "heads", None))
    k = with_logical_constraint(k, ("batch", None, "kv", None))
    v = with_logical_constraint(v, ("batch", None, "kv", None))
    out = attention(q, k, v, causal=True, impl=cfg.attention_impl,
                    chunk=cfg.attention_chunk)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))


def mla_scale(cfg: ModelConfig) -> float:
    """The softmax scale of latent attention: (dn + dr)^-1/2, times
    mscale(mscale_all_dim)^2 under YaRN."""
    scale = (cfg.head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_yarn is not None and cfg.rope_yarn.mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_yarn.factor,
                             cfg.rope_yarn.mscale_all_dim) ** 2
    return scale


def mla_block(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array,
              cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1),
    without query compression. x: (B, S, D) normed input; cos, sin over the
    rope width dr.

        q            = x . Wq                      (H, dn + dr) = [q_nope, q_pe]
        [c_kv, k_pe] = x . Wkv_a                   (r + dr)
        c_kv         = RMSNorm(c_kv)
        [k_nope, v]  = c_kv . Wkv_b                (H, dn + dv)
        q_pe, k_pe   = rope(q_pe), rope(k_pe)      k_pe: one key for all heads
        o_h          = causal softmax([q_nope, q_pe]_h . [k_nope_h, k_pe]
                                      * mla_scale) . v_h
        out          = Wo . concat_h(o_h)
    """
    cd = cfg.cdtype
    dn, r = cfg.head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cd))
        kv_a = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"].astype(cd))
        c_kv = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(kv_a[..., None, r:], cos, sin)        # (B, S, 1, dr)
        kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(cd))
        q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], cos, sin)],
                            axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, kv.shape[:3] + k_pe.shape[-1:])],
            axis=-1)
        q = with_logical_constraint(q, ("batch", "seq_sp", "heads", None))
        out = attention(q, k, kv[..., dn:], causal=True,
                        impl=cfg.attention_impl, chunk=cfg.attention_chunk,
                        scale=mla_scale(cfg))
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(cd))


def rope_table(cfg: ModelConfig, positions: jax.Array):
    """(cos, sin) over the rotated width: the whole head, or MLA's dr."""
    if cfg.mla:
        return rope_angles(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                           cfg.rope_yarn)
    return rope_angles(positions, cfg.head_dim, cfg.rope_theta)


def dense_ffn(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    cd = cfg.cdtype
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cd))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cd))
    mid = swiglu(g, u)
    mid = with_logical_constraint(mid, ("batch", None, "mlp"))
    return jnp.einsum("bsf,fd->bsd", mid, p["w_down"].astype(cd))


def decoder_layer(cfg: ModelConfig, lp: Dict[str, jax.Array], h: jax.Array,
                  cos: jax.Array, sin: jax.Array, moe: bool
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One pre-norm residual layer. Returns (h, stats): ``aux``, the balance
    loss, and for an expert layer its counts (``moe.moe_ffn``)."""
    x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
    h = h + (mla_block if cfg.mla else attn_block)(cfg, lp, x, cos, sin)
    x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    if moe:
        y, aux, counts = moe_ffn(cfg, lp, x)
        stats = {"aux": aux, **counts}
    else:
        y, stats = dense_ffn(cfg, lp, x), {"aux": jnp.zeros((), jnp.float32)}
    h = h + y
    h = with_logical_constraint(h, ("batch", "seq_res", None))
    return h, stats


def layer_runs(cfg: ModelConfig, params) -> List[Tuple[Dict, bool]]:
    """The stack's runs of equal layers in order, each (params, moe)."""
    runs = [(params["layers"], cfg.family == "moe")]
    if cfg.first_dense_layers:
        runs.insert(0, (params["dense_layers"], False))
    return runs


def _scan_layers(cfg: ModelConfig, layer_params, h, cos, sin, moe: bool):
    """Scan h through one run's stacked layer params (with optional full
    remat). Returns h and each layer's stats, stacked."""

    def body(carry, lp):
        return decoder_layer(cfg, lp, carry, cos, sin, moe)

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.scan_layers:
        return jax.lax.scan(body, h, layer_params)
    L = jax.tree_util.tree_leaves(layer_params)[0].shape[0]
    stats = []
    for i in range(L):
        lp = jax.tree_util.tree_map(lambda a: a[i], layer_params)
        h, st = body(h, lp)
        stats.append(st)
    return h, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)


# ---------------------------------------------------------------------------
# Forward / prefill / decode
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, params, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Training/eval forward pass. Returns (logits, aux_loss, counts):
    ``counts`` are the expert layers' ``moe_rows_local`` (summed over them)
    and ``moe_rows_max`` (the fullest of them), empty without experts.

    tokens: (B, S) int32 — or (B, S, K) for audio. frontend_embeds: (B, P, D)
    precomputed modality embeddings prepended to the token embeddings.
    """
    h = _embed_tokens(cfg, params, tokens)
    if frontend_embeds is not None:
        h = jnp.concatenate([frontend_embeds.astype(h.dtype), h], axis=1)
    B, S = h.shape[:2]
    h = with_logical_constraint(h, ("batch", None, None))
    cos, sin = rope_table(cfg, jnp.arange(S))
    cos, sin = cos[None], sin[None]  # (1, S, dh/2)
    aux, counts = None, {}
    for layer_params, moe in layer_runs(cfg, params):
        h, stats = _scan_layers(cfg, layer_params, h, cos, sin, moe)
        run_aux = jnp.sum(stats.pop("aux"))
        aux = run_aux if aux is None else aux + run_aux
        if stats:
            counts = {"moe_rows_local": jnp.sum(stats["moe_rows_local"]),
                      "moe_rows_max": jnp.max(stats["moe_rows_max"])}
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)
    return logits, aux, counts


def init_cache_specs(cfg: ModelConfig, batch: int, max_seq: int):
    """Abstract KV-cache structure for AOT lowering: (L, B, T, G, dh) x2.

    Logical axes: cache sequence dim shards over "model" (flash-decode style);
    batch over ("pod","data").
    """
    L, G, dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    shape = (L, batch, max_seq, G, dh)
    axes = ("layers", "batch", "cache_seq", "kv", None)
    return {
        "k": (jax.ShapeDtypeStruct(shape, cfg.cdtype), axes),
        "v": (jax.ShapeDtypeStruct(shape, cfg.cdtype), axes),
    }


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    specs = init_cache_specs(cfg, batch, max_seq)
    return {k: jnp.zeros(s.shape, s.dtype) for k, (s, _a) in specs.items()}


def _ffn(cfg: ModelConfig, lp, x: jax.Array, moe: bool) -> jax.Array:
    return moe_ffn(cfg, lp, x)[0] if moe else dense_ffn(cfg, lp, x)


def _serving_runs(cfg: ModelConfig, params):
    if cfg.mla:
        raise NotImplementedError("serving latent attention: there is no "
                                  "MLA cache")
    return layer_runs(cfg, params)


def _split_runs(cache: jax.Array, runs) -> List[jax.Array]:
    """The cache's (L, ...) layer axis cut into one slice per run."""
    if len(runs) == 1:
        return [cache]
    sizes = [jax.tree_util.tree_leaves(lp)[0].shape[0] for lp, _ in runs]
    return jnp.split(cache, list(np.cumsum(sizes)[:-1]))


def _join_runs(parts: List[jax.Array]) -> jax.Array:
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def prefill(cfg: ModelConfig, params, tokens: jax.Array,
            frontend_embeds: Optional[jax.Array] = None):
    """Forward pass that also materializes the KV cache. Returns
    (logits_last, cache) — cache shaped (L, B, S, G, dh)."""
    runs = _serving_runs(cfg, params)
    h = _embed_tokens(cfg, params, tokens)
    if frontend_embeds is not None:
        h = jnp.concatenate([frontend_embeds.astype(h.dtype), h], axis=1)
    B, S = h.shape[:2]
    cos, sin = rope_angles(jnp.arange(S), cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[None], sin[None]
    cd = cfg.cdtype

    def make_body(moe: bool):
        def body(carry, lp):
            hh = carry
            x = rms_norm(hh, lp["attn_norm"], cfg.norm_eps)
            q, k, v = _qkv(cfg, lp, x, cos, sin)
            q = with_logical_constraint(q, ("batch", "seq_sp", "heads", None))
            # pin attention-side k/v shardings so the cache_seq constraint
            # below does not back-propagate (would force an involuntary
            # all-gather)
            k = with_logical_constraint(k, ("batch", None, "kv", None))
            v = with_logical_constraint(v, ("batch", None, "kv", None))
            out = attention(q, k, v, causal=True, impl=cfg.attention_impl,
                            chunk=cfg.attention_chunk)
            hh = hh + jnp.einsum("bshk,hkd->bsd", out, lp["wo"].astype(cd))
            x = rms_norm(hh, lp["mlp_norm"], cfg.norm_eps)
            hh = hh + _ffn(cfg, lp, x, moe)
            kc = with_logical_constraint(k, ("batch", "cache_seq", "kv", None))
            vc = with_logical_constraint(v, ("batch", "cache_seq", "kv", None))
            return hh, (kc, vc)

        if cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        return body

    ks, vs = [], []
    for lp, moe in runs:
        h, (kc, vc) = jax.lax.scan(make_body(moe), h, lp)
        ks.append(kc)
        vs.append(vc)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h[:, -1:])[:, 0]  # (B, V) / (B, K, V)
    return logits, {"k": _join_runs(ks), "v": _join_runs(vs)}


def decode_step(cfg: ModelConfig, params, cache, tokens: jax.Array,
                pos: jax.Array):
    """One-token decode against the KV cache.

    tokens: (B,) int32 (or (B, K) audio); pos: scalar int32 — current position.
    Returns (logits, new_cache).
    """
    runs = _serving_runs(cfg, params)
    if cfg.family == "audio":
        tok = tokens[:, None, :]  # (B, 1, K)
    else:
        tok = tokens[:, None]     # (B, 1)
    h = _embed_tokens(cfg, params, tok)  # (B, 1, D)
    cd = cfg.cdtype
    cos, sin = rope_angles(pos[None], cfg.head_dim, cfg.rope_theta)
    cos, sin = cos[None], sin[None]  # (1, 1, dh/2)

    def make_body(moe: bool):
        def body(carry, xs):
            hh = carry
            lp, kc, vc = xs
            x = rms_norm(hh, lp["attn_norm"], cfg.norm_eps)
            q, k, v = _qkv(cfg, lp, x, cos, sin)
            kc = cache_update(kc, k, pos)
            vc = cache_update(vc, v, pos)
            out = decode_attention(q[:, 0], kc, vc, pos)[:, None]
            hh = hh + jnp.einsum("bshk,hkd->bsd", out, lp["wo"].astype(cd))
            x = rms_norm(hh, lp["mlp_norm"], cfg.norm_eps)
            return hh + _ffn(cfg, lp, x, moe), (kc, vc)
        return body

    # "readonly_fused" (§Perf): the scan-carried cache is double-buffered
    # by XLA (xs in + ys out ~= 2x cache in temp). Instead the scan READS
    # the cache (xs) and emits only each layer's new (B, 1, G, dh) KV as
    # ys; attention combines the stale cache (masked < pos) with the new
    # token analytically; ONE fused elementwise select then writes all
    # layers' updates — aliasable with the donated input buffer.
    def make_body_ro(moe: bool):
        def body_ro(carry, xs):
            hh = carry
            lp, kc, vc = xs
            x = rms_norm(hh, lp["attn_norm"], cfg.norm_eps)
            q, k, v = _qkv(cfg, lp, x, cos, sin)
            out = decode_attention_readonly(q[:, 0], kc, vc, k[:, 0], v[:, 0],
                                            pos)[:, None]
            hh = hh + jnp.einsum("bshk,hkd->bsd", out, lp["wo"].astype(cd))
            x = rms_norm(hh, lp["mlp_norm"], cfg.norm_eps)
            return hh + _ffn(cfg, lp, x, moe), (k[:, 0], v[:, 0])
        return body_ro

    carry = cfg.decode_cache_mode == "scan_carry"
    make = make_body if carry else make_body_ro
    ks, vs = [], []
    for (lp, moe), kc, vc in zip(runs, _split_runs(cache["k"], runs),
                                 _split_runs(cache["v"], runs)):
        h, (k_out, v_out) = jax.lax.scan(make(moe), h, (lp, kc, vc))
        ks.append(k_out)
        vs.append(v_out)
    if carry:
        new_cache = {"k": _join_runs(ks), "v": _join_runs(vs)}
    else:
        k_upd, v_upd = _join_runs(ks), _join_runs(vs)
        T = cache["k"].shape[2]
        hit = (jnp.arange(T) == pos)[None, None, :, None, None]
        new_cache = {
            "k": jnp.where(hit, k_upd[:, :, None].astype(cache["k"].dtype),
                           cache["k"]),
            "v": jnp.where(hit, v_upd[:, :, None].astype(cache["v"].dtype),
                           cache["v"]),
        }
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)[:, 0]
    return logits, new_cache
