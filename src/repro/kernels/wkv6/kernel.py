"""Chunked WKV6 recurrence, Pallas TPU.

Grid (b, h, ic) with the chunk index minor: the (dh x dh) recurrence state
lives in VMEM scratch across the whole sequence sweep of one (b, h) pair —
the defining TPU adaptation (on GPU this state sits in registers/SMEM per
thread block; on TPU it is a VMEM-resident tile feeding the MXU).

Per chunk (C = chunk len):
  intra-chunk: pairwise per-channel decay D[t,s,i] = exp(ecw_t - cw_s) (<= 1,
               numerically safe), scores = sum_i r k D, strictly-lower tri +
               diag(u) bonus; y_intra = scores @ v
  inter-chunk: y += (r * exp(ecw)) @ S
  state:       S <- exp(cw_C) * S + (k * exp(cw_C - cw))^T @ v

VMEM per step (C = 32, dh = 64, fp32): tiles ~4 x 8 KiB, D tensor
C*C*dh*4 = 256 KiB, state 16 KiB — well under budget; dh = 64 matches the
RWKV6 head size so the MXU sees (32..64 x 64) matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, ca: int, cb: int):
    """f32 matmul contracting ``a``'s dim ``ca`` with ``b``'s dim ``cb``, at
    full f32 precision: Mosaic's default rounds f32 operands to bf16, which
    the recurrence state accumulates chunk after chunk."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref, s_sc, *,
                chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_sc[...] = jnp.zeros_like(s_sc)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, dh)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (1, dh)

    lw = jnp.log(jnp.maximum(w, 1e-12))
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    # (Mosaic has no cumsum)
    cw = _dot((row >= col).astype(jnp.float32), lw, 1, 0)  # (C, dh)
    ecw = cw - lw                                # exclusive

    # pairwise decay for s < t (exponents <= 0; the clamp keeps the masked
    # upper triangle finite), strictly lower triangular after the sum
    diff = ecw[:, None, :] - cw[None, :, :]      # (C, C, dh)
    dec = jnp.exp(jnp.minimum(diff, 0.0))
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * dec, axis=-1)  # (C, C)
    scores = jnp.where(row > col, scores, 0.0)
    diag = jnp.sum(r * k * u, axis=-1)                              # (C,)
    y = _dot(scores, v, 1, 0)
    y = y + diag[:, None] * v
    # inter-chunk
    rdec = r * jnp.exp(ecw)
    y = y + _dot(rdec, s_sc[...], 1, 0)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update
    total = cw[-1:, :]                           # (1, dh)
    kdec = k * jnp.exp(total - cw)               # (C, dh)
    s_sc[...] = jnp.exp(total[0])[:, None] * s_sc[...] + _dot(kdec, v, 0, 0)

    @pl.when(ic == nc - 1)
    def _emit_state():
        sout_ref[0, 0] = s_sc[...]


def wkv6_fwd(r, k, v, w, u, chunk: int = 32, interpret: bool = True):
    """r/k/v/w: (B, S, H, dh) (w = per-step decay in (0,1)); u: (H, dh).
    Returns (y (B, S, H, dh), state (B, H, dh, dh) fp32)."""
    B, S, H, dh = r.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        zf = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v = zf(r), zf(k), zf(v)
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    T = r.shape[1]
    nc = T // chunk
    # kernel layout: (B, H, S, dh)
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    rk, kk, vk, wk = tr(r), tr(k), tr(v), tr(w)

    y, state = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk, nc=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dh), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, dh), lambda b, h, ic: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dh), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, dh), r.dtype),
            jax.ShapeDtypeStruct((B, H, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
    )(rk, kk, vk, wk, u.reshape(H, 1, dh))   # block (1, 1, dh): last two
                                             # dims equal the array's
    y = jnp.transpose(y, (0, 2, 1, 3))[:, :S]
    return y, state
