"""Causal GQA flash-attention forward, Pallas TPU.

Layout: the wrapper transposes q/k/v to (B, H, S, dh) so every block's last
two dims are (block, dh) — Mosaic requires them (8, 128)-aligned or equal to
the array's.

Tiling (per grid step (b, h, iq, ik)):
  * q tile   (block_q, dh)   VMEM-resident across the ik loop (minor grid dim)
  * k/v tile (block_k, dh)   streamed HBM -> VMEM per step; the kv-head index
                             is derived in the BlockSpec index_map (h * G // H)
                             so GQA never materializes repeated KV
  * scratch  m/l (block_q, 1) and acc (block_q, dh) fp32 persist across ik

VMEM budget per step (block_q = block_k = 128, dh = 128, bf16 in / fp32 acc):
  q 32 KiB + k 32 KiB + v 32 KiB + acc 64 KiB + s 64 KiB ~= 0.25 MiB << 16 MiB,
  leaving headroom for double-buffered pipelines. MXU dims (128 x dh) aligned.

Causality is handled by masking; fully-masked tiles short-circuit via pl.when.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               scale: float, block_q: int, block_k: int, causal: bool,
               nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = iq * block_q
    k_start = ik * block_k
    # skip tiles strictly above the diagonal
    live = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_sc[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, block_q: int = 128,
                        block_k: int = 128,
                        interpret: bool = True) -> jax.Array:
    """q: (B, S, H, dh); k/v: (B, T, G, dh) with H % G == 0 -> (B, S, H, dh).

    The kernel sees (B, H, S, dh) so each block's last two dims are
    (block, dh), the layout Mosaic tiles."""
    B, S, H, dh = q.shape
    T, G = k.shape[1], k.shape[2]
    assert H % G == 0, (H, G)
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    nq, nk = S // block_q, T // block_k
    scale = 1.0 / np.sqrt(dh)

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, nk=nk)

    heads_first = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik, G=G, H=H: (b, h * G // H, ik, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda b, h, iq, ik, G=G, H=H: (b, h * G // H, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)
