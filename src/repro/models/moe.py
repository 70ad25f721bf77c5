"""Fine-grained Mixture-of-Experts FFN (DeepSeekMoE, DeepSeek-V2, Qwen3-MoE).

One expert layer, told which experts it holds: ``E_held`` experts from
``e0`` of the router's ``E`` (on one device, ``cfg.moe_experts_held`` from
expert 0). Every token is routed over all ``E`` experts;
only the choices of held experts are computed here. What the other experts
add is the part of the chips that hold them (expert parallelism), and this
layer stands in for none of it.

  probs      = softmax(x . W_router)              (T, E), float32
  gates, idx = top_k(probs, K)                    greedy; renormalised to sum
                                                  to 1 iff cfg.moe_norm_topk
  y = sum over the held choices (t, k) of gates[t, k] * expert_idx[t,k](x_t)
      + shared(x)
  expert_e(x) = W_down[e] . (silu(W_gate[e] . x) * (W_up[e] . x))

No token is dropped at any imbalance. The choices of held experts are sorted
by expert into a buffer that holds the worst case, T * min(K, E_held) rows
(a token picks each expert at most once), and each of the gate, up and down
projections is one grouped matmul (``jax.lax.ragged_dot``) over the held
experts' groups of rows; the buffer's rows past the routed ones belong to no
group. The shared experts are one dense SwiGLU of width
``moe_num_shared * moe_d_ff``.

Balance loss, DeepSeek-V2's per sequence (arXiv:2405.04434 §2.1.3):
``moe_aux_coef * mean_b sum_e f_e * P_e`` with ``f_e = count_e * E / (S * K)``
the sequence's share of choices of expert e, scaled so that uniform routing
gives 1, and ``P_e`` the sequence's mean router probability of e.

Under a mesh with a "model" axis each model shard holds ``E_held / |model|``
of the experts and runs the same body with its own ``e0``; the partial
outputs are summed over "model". Scopes for the trace: ``moe.route``,
``moe.experts``, ``moe.shared``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, swiglu
from repro.models.config import ModelConfig
from repro.sharding.specs import current_rules


def moe_param_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    D, E, F = cfg.d_model, cfg.experts_held, cfg.moe_d_ff
    specs = {
        "router": ParamSpec((L, D, cfg.moe_num_experts),
                            ("layers", "embed", None)),
        "w_gate": ParamSpec((L, E, D, F), ("layers", "experts", "embed", "mlp")),
        "w_up": ParamSpec((L, E, D, F), ("layers", "experts", "embed", "mlp")),
        "w_down": ParamSpec((L, E, F, D), ("layers", "experts", "mlp", "embed")),
    }
    if cfg.moe_num_shared:
        Fs = cfg.moe_d_ff * cfg.moe_num_shared
        specs.update({
            "sh_gate": ParamSpec((L, D, Fs), ("layers", "embed", "mlp")),
            "sh_up": ParamSpec((L, D, Fs), ("layers", "embed", "mlp")),
            "sh_down": ParamSpec((L, Fs, D), ("layers", "mlp", "embed")),
        })
    return specs


def route(cfg: ModelConfig, router: jax.Array, x: jax.Array):
    """x (B, S, D) -> gates (B, S, K) float32, idx (B, S, K) int32 over the
    router's E experts, and the balance loss."""
    E, K = cfg.moe_num_experts, cfg.moe_top_k
    S = x.shape[1]
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, K)
        if cfg.moe_norm_topk:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        counts = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                         axis=(1, 2))                            # (B, E)
        f = counts * (E / (S * K))
        aux = cfg.moe_aux_coef * jnp.mean(
            jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))
    return gates, idx, aux


def routed_experts(cfg: ModelConfig, x: jax.Array, gates: jax.Array,
                   idx: jax.Array, wg: jax.Array, wu: jax.Array,
                   wd: jax.Array, e0) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed output, dropping nothing.

    x (T, D); gates, idx (T, K); wg, wu (E_held, D, F), wd (E_held, F, D)
    hold experts [e0, e0 + E_held). Returns y (T, D) and the rows each held
    expert computed, (E_held,) int32."""
    cd = cfg.cdtype
    T, D = x.shape
    K = idx.shape[-1]
    n = wg.shape[0]
    R = T * min(K, n)
    with jax.named_scope("moe.experts"):
        local = idx.reshape(-1) - e0
        held = (local >= 0) & (local < n)
        eid = jnp.where(held, local, n)                          # (T*K,)
        order = jnp.argsort(eid, stable=True)[:R]
        rows = jnp.bincount(eid, length=n + 1)[:n].astype(jnp.int32)
        routed = jnp.arange(R) < jnp.sum(rows)
        # rows past the routed ones belong to no group: zero them so that
        # nothing flows back into the tokens they were gathered from
        xs = jnp.where(routed[:, None], x.astype(cd)[order // K], 0)
        h = jax.lax.ragged_dot(xs, wg.astype(cd), rows)
        u = jax.lax.ragged_dot(xs, wu.astype(cd), rows)
        out = jax.lax.ragged_dot(swiglu(h, u), wd.astype(cd), rows)
        # back to (token, choice) order; other experts' choices read zeros
        slot = jnp.zeros((T * K,), jnp.int32).at[order].set(
            jnp.arange(R, dtype=jnp.int32))
        slot = jnp.where(held, slot, R)
        out = jnp.concatenate([out, jnp.zeros((1, D), cd)], axis=0)
        y = jnp.einsum("tkd,tk->td", out[slot].reshape(T, K, D),
                       jnp.where(held.reshape(T, K), gates, 0).astype(cd))
    return y, rows


def _shared(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    cd = cfg.cdtype
    with jax.named_scope("moe.shared"):
        g = jnp.einsum("bsd,df->bsf", x, p["sh_gate"].astype(cd))
        u = jnp.einsum("bsd,df->bsf", x, p["sh_up"].astype(cd))
        return jnp.einsum("bsf,fd->bsd", swiglu(g, u),
                          p["sh_down"].astype(cd))


def _moe_ffn_mesh(cfg: ModelConfig, p, x: jax.Array):
    """Expert-data-local dispatch: every (data, model) shard routes its
    LOCAL tokens through its LOCAL experts — tokens are replicated across the
    model axis already, so dispatch needs NO communication; the only
    collective is the partial-output psum over "model" (the same all-reduce a
    dense TP FFN pays). FSDP weight gathers happen explicitly inside the body.
    """
    rules = current_rules()
    mesh = rules.mesh
    dax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    E_loc = cfg.experts_held // mesh.shape["model"]
    cd = cfg.cdtype

    def body(x_loc, router_l, wg_l, wu_l, wd_l):
        gather = lambda w, ax: jax.lax.all_gather(
            w, dax, axis=ax, tiled=True) if dax else w
        router = gather(router_l, 0)
        wg, wu, wd = gather(wg_l, 1), gather(wu_l, 1), gather(wd_l, 2)
        e0 = jax.lax.axis_index("model") * E_loc
        B_loc, S, D = x_loc.shape
        gates, idx, aux = route(cfg, router, x_loc)
        y, rows = routed_experts(cfg, x_loc.reshape(B_loc * S, D),
                                 gates.reshape(B_loc * S, -1),
                                 idx.reshape(B_loc * S, -1), wg, wu, wd, e0)
        axes = dax + ("model",)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, axes)
        counts = {"moe_rows_local": jax.lax.psum(jnp.sum(rows), axes),
                  "moe_rows_max": jax.lax.pmax(jnp.max(rows), axes)}
        return y.reshape(B_loc, S, D), aux, counts

    P = jax.sharding.PartitionSpec
    in_specs = (
        rules.spec(("batch", None, None)),
        rules.spec(("embed", None)),            # router (D, E)
        rules.spec(("experts", "embed", "mlp"), None),
        rules.spec(("experts", "embed", "mlp"), None),
        rules.spec(("experts", "mlp", "embed"), None),
    )
    out_specs = (rules.spec(("batch", None, None)), P(),
                 {"moe_rows_local": P(), "moe_rows_max": P()})
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(x.astype(cd), p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_ffn(cfg: ModelConfig, p: Dict[str, jax.Array],
            x: jax.Array) -> Tuple[jax.Array, jax.Array, Dict]:
    """x: (B, S, D) -> (y, aux_loss, counts). ``counts``: the choices this
    layer computed here (``moe_rows_local``) and the fullest held expert's
    rows (``moe_rows_max``)."""
    rules = current_rules()
    if rules is not None and "model" in rules.mesh.axis_names:
        y, aux, counts = _moe_ffn_mesh(cfg, p, x)
    else:
        B, S, D = x.shape
        gates, idx, aux = route(cfg, p["router"], x)
        y, rows = routed_experts(cfg, x.reshape(B * S, D),
                                 gates.reshape(B * S, -1),
                                 idx.reshape(B * S, -1), p["w_gate"],
                                 p["w_up"], p["w_down"], 0)
        y = y.reshape(B, S, D)
        counts = {"moe_rows_local": jnp.sum(rows),
                  "moe_rows_max": jnp.max(rows)}
    if cfg.moe_num_shared:
        y = y + _shared(cfg, p, x.astype(cfg.cdtype))
    return y.astype(x.dtype), aux, counts
