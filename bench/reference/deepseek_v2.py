"""Plain reference of DeepSeek-V2's training step (arXiv:2405.04434, the
Lite model's published config.json), at one chip's share of expert
parallelism: float32 ``jax.numpy`` at ``highest`` matmul precision, written
from the paper and the published config, importing nothing of the program.

It also makes the benchmark's weights: ``make_init`` draws them from the
run's seed in one jitted call, in the layout the program's step takes
(``embed``, ``unembed``, ``final_norm``, a ``dense_layers`` dict for the
leading dense layers and a ``layers`` dict for the expert layers, whose
leaves stack the layers on a leading axis).

The block, per layer (dn = qk_nope_head_dim, dr = qk_rope_head_dim,
dv = v_head_dim, r = kv_lora_rank, H heads):

    x = n1(h)
    q = x . Wq  -> (H, dn + dr) = [q_nope, q_pe]
    [c_kv, k_pe] = x . Wkv_a  -> (r + dr);  c_kv = RMSNorm(c_kv)
    [k_nope, v] = c_kv . Wkv_b  -> (H, dn + dv)
    q_pe, k_pe = rope(q_pe), rope(k_pe)        one k_pe shared by the heads
    o_h = causal softmax([q_nope, q_pe]_h . [k_nope_h, k_pe] * scale) . v_h
    h += Wo . concat_h(o_h)
    h += ffn(n2(h))

with scale = (dn + dr)^-1/2 * mscale^2, mscale = 0.1 * mscale_all_dim *
ln(factor) + 1, and YaRN rotary frequencies (``yarn_inv_freq``); cos and sin
are scaled by mscale(mscale) / mscale(mscale_all_dim), 1 here. The first
``first_k_dense_replace`` layers have a dense SwiGLU FFN of
``intermediate_size``; the others an expert layer:

    probs = softmax(x . W_router) over the router's experts (float32)
    gates, idx = greedy top-k of probs (raw, not renormalised, when
                 norm_topk_prob is false; times routed_scaling_factor 1)
    ffn(x) = sum_k gates_k * expert_idx_k(x) + shared(x)
    aux = aux_loss_alpha * sum_e f_e * P_e per sequence,
          f_e = count_e * E / (S * K),  P_e = mean_s probs[s, e]

Only the experts held here (``n_routed_experts`` of them, from expert 0)
are computed, each over every token with its gate (0 where it was not
chosen); what the others add is left out, as the program leaves it out.
The shared experts (``n_shared_experts * moe_intermediate_size`` wide) are
whole. loss = mean next-token cross-entropy + the expert layers' aux.

Departures, each also the program's: rotary embedding on the two halves of
the rope width (rotate-half) where the published code interleaves pairs,
which with seeded weights is only a fixed relabelling of Wq's and Wkv_a's
rope columns; the head is a separate matrix; the vocabulary is the
configuration's slice, so logits and loss run over the slice only; weight
decay applies to every leaf stored with two or more dimensions, which takes
in the stacked per-layer norm scales.

``matmul`` selects the arithmetic of every product, as in ``granite``:
``"f32"`` is the reference, ``"fp8"`` the lower-precision control.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# the arithmetic, the optimizer and the seed as Granite's reference has them;
# leaf_paths, leaf_norms and seed_words are this module's contract too
from bench.reference.granite import (global_norm, leaf_norms,  # noqa: F401
                                     leaf_paths, learning_rate, make_adamw,
                                     make_einsum, rms_norm, seed_words)

LEAVES = ("embed", "unembed", "final_norm")
ATTN_LEAVES = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
               "mlp_norm")
DENSE_LEAVES = ATTN_LEAVES + ("w_gate", "w_up", "w_down")
MOE_LEAVES = ATTN_LEAVES + ("router", "w_gate", "w_up", "w_down", "sh_gate",
                            "sh_up", "sh_down")

#: the CPU tests' size: the same layout with tiny widths, one dense and one
#: expert layer, 2 experts held of the router's 4, top 2, q.k width (24)
#: unequal to v's (16)
SMOKE = {
    "model": dict(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=96, moe_intermediate_size=32,
                  n_routed_experts=2, router_experts=4,
                  num_experts_per_tok=2, n_shared_experts=1,
                  num_hidden_layers=2, vocab_size=257),
    # the smoke size's own limits, at lower^1/3 * upper^2/3 between what
    # sound runs read on the CPU (six seeds: loss 2.1e-4, gradient 4.9e-3,
    # change 2.3e-3) and the least the fp8 control read on three (1.7e-3,
    # 1.9e-2, 7.5e-3); the half batch read 6.1e-3, 0.11, 0.16 and more
    "limits": dict(loss_gap=8e-4, grad_gap=1.2e-2, change_gap=5e-3),
}


def program_config(cfg: Mapping):
    """The program's ``ModelConfig`` for a DeepSeek-V2 configuration."""
    from repro.models import ModelConfig
    from repro.models.common import YarnScaling
    m, prec = cfg["model"], cfg["precision"]
    unsupported = {k: m.get(k) for k, want in (
        ("q_lora_rank", None), ("scoring_func", "softmax"),
        ("topk_method", "greedy"), ("n_group", 1), ("topk_group", 1),
        ("routed_scaling_factor", 1), ("moe_layer_freq", 1),
        ("attention_bias", False), ("hidden_act", "silu"))
        if m.get(k) != want}
    if unsupported or m["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"{cfg['name']}: settings the program does not "
                         f"run: {unsupported or m['rope_scaling']}")
    rs = m["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=m["num_hidden_layers"],
        first_dense_layers=m["first_k_dense_replace"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"],
        head_dim=m["qk_nope_head_dim"], kv_lora_rank=m["kv_lora_rank"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        moe_num_experts=m["router_experts"],
        moe_experts_held=m["n_routed_experts"],
        moe_top_k=m["num_experts_per_tok"],
        moe_num_shared=m["n_shared_experts"],
        moe_d_ff=m["moe_intermediate_size"],
        moe_norm_topk=m["norm_topk_prob"], moe_aux_coef=m["aux_loss_alpha"],
        rope_theta=m["rope_theta"],
        rope_yarn=YarnScaling(
            factor=rs["factor"],
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        norm_eps=m["rms_norm_eps"], tie_embeddings=m["tie_word_embeddings"],
        param_dtype=prec["params"], compute_dtype=prec["compute"])


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _dims(m: Mapping):
    return (m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])


def shapes(m: Mapping) -> Dict:
    """Leaf shapes of the parameter tree."""
    D, H, r, dn, dr, dv = _dims(m)
    V, F = m["vocab_size"], m["intermediate_size"]
    E, Fe = m["n_routed_experts"], m["moe_intermediate_size"]
    Fs = m["n_shared_experts"] * Fe
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense

    def attn(L):
        return {"attn_norm": (L, D), "wq": (L, D, H, dn + dr),
                "wkv_a": (L, D, r + dr), "kv_norm": (L, r),
                "wkv_b": (L, r, H, dn + dv), "wo": (L, H, dv, D),
                "mlp_norm": (L, D)}

    return {
        "embed": (V, D), "unembed": (D, V), "final_norm": (D,),
        "dense_layers": {**attn(n_dense), "w_gate": (n_dense, D, F),
                         "w_up": (n_dense, D, F), "w_down": (n_dense, F, D)},
        "layers": {**attn(n_moe), "router": (n_moe, D, m["router_experts"]),
                   "w_gate": (n_moe, E, D, Fe), "w_up": (n_moe, E, D, Fe),
                   "w_down": (n_moe, E, Fe, D), "sh_gate": (n_moe, D, Fs),
                   "sh_up": (n_moe, D, Fs), "sh_down": (n_moe, Fs, D)}}


def _std(group: str, name: str, m: Mapping) -> float:
    """Init scale: 1 for the embedding, 1/sqrt(fan-in) for weights.

    The embedding is not drawn small (Granite's 0.02): with random weights
    attention is near uniform, so its output is much the same at every
    position, and a small embedding leaves that common part to decide the
    routing. The routers then sent most of a layer's tokens to the same
    experts, and how many of them were held here changed with the seed."""
    D, H, r, _, _, dv = _dims(m)
    if name == "embed":
        return 1.0
    down = {"dense_layers": m["intermediate_size"],
            "layers": m["moe_intermediate_size"]}.get(group)
    return {"wkv_b": r, "wo": H * dv, "w_down": down,
            "sh_down": m["n_shared_experts"] * m["moe_intermediate_size"]
            }.get(name, D) ** -0.5


def make_init(m: Mapping) -> Callable:
    """``init(seed_words) -> params``, float32, norm scales at one."""
    tree = shapes(m)

    def init(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])

        def leaf(i, group, name, shape):
            if name.endswith("norm"):
                return jnp.ones(shape, jnp.float32)
            return jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * _std(group, name, m)

        out = {n: leaf(i, "", n, tree[n]) for i, n in enumerate(LEAVES)}
        i = len(LEAVES)
        for group, names in (("dense_layers", DENSE_LEAVES),
                             ("layers", MOE_LEAVES)):
            out[group] = {n: leaf(i + j, group, n, tree[group][n])
                          for j, n in enumerate(names)}
            i += len(names)
        return out

    return init


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def yarn_inv_freq(m: Mapping) -> np.ndarray:
    """YaRN's inverse frequencies over the rope width, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them."""
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]
    rs = m["rope_scaling"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (rs["factor"] * base ** (np.arange(0, dim, 2) / dim))

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(x, positions, inv_freq, mult):
    """Rotary embedding on (S, heads, dr), rotate-half convention."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    c = (jnp.cos(ang) * mult)[:, None, :]
    s = (jnp.sin(ang) * mult)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention_scale(m: Mapping) -> float:
    rs = m["rope_scaling"]
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 \
        * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def mla(m: Mapping, ein: Callable, x, lp, pos):
    """Latent attention of one sequence's normed x (S, D)."""
    D, H, r, dn, dr, dv = _dims(m)
    rs = m["rope_scaling"]
    inv_freq = jnp.asarray(yarn_inv_freq(m))
    mult = _mscale(rs["factor"], rs["mscale"]) \
        / _mscale(rs["factor"], rs["mscale_all_dim"])
    scale = attention_scale(m)
    S = x.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))

    # one head at a time, each recomputed in the backward pass, so the
    # scores of one head live
    @jax.checkpoint
    def head(args):
        qh, kh, vh = args
        s = ein("qd,kd->qk", qh, kh) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return ein("qk,kd->qd", p, vh)

    q = ein("sd,dhk->shk", x, lp["wq"])
    kv_a = ein("sd,dk->sk", x, lp["wkv_a"])
    c_kv = rms_norm(kv_a[:, :r], lp["kv_norm"], m["rms_norm_eps"])
    k_pe = rope(kv_a[:, None, r:], pos, inv_freq, mult)       # (S, 1, dr)
    kv = ein("sr,rhk->shk", c_kv, lp["wkv_b"])
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos, inv_freq, mult)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))],
                        axis=-1)
    o = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                           jnp.moveaxis(kv[..., dn:], 1, 0)))
    return ein("shk,hkd->sd", jnp.moveaxis(o, 0, 1), lp["wo"])


def _swiglu(ein: Callable, x, wg, wu, wd):
    return ein("sf,fd->sd", jax.nn.silu(ein("sd,df->sf", x, wg))
               * ein("sd,df->sf", x, wu), wd)


def expert_ffn(m: Mapping, ein: Callable, x, lp):
    """One sequence's normed x (S, D) through an expert layer: the held
    experts' part and the shared experts, and the sequence's balance loss."""
    S = x.shape[0]
    E, K = m["router_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(ein("sd,de->se", x, lp["router"]), axis=-1)
    gates, idx = jax.lax.top_k(probs, K)
    if m["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * m["routed_scaling_factor"]
    # each held expert's gate per token: 0 where it was not chosen
    w = jnp.sum(gates[..., None] * jax.nn.one_hot(idx, E), axis=1)  # (S, E)
    y = _swiglu(ein, x, lp["sh_gate"], lp["sh_up"], lp["sh_down"])
    for e in range(m["n_routed_experts"]):
        y = y + w[:, e:e + 1] * _swiglu(ein, x, lp["w_gate"][e],
                                        lp["w_up"][e], lp["w_down"][e])
    f = jnp.sum(jax.nn.one_hot(idx, E), axis=(0, 1)) * E / (S * K)
    return y, m["aux_loss_alpha"] * jnp.sum(f * jnp.mean(probs, axis=0))


def row_loss_fn(m: Mapping, matmul: str = "f32") -> Callable:
    """``loss(params, tokens)`` of one sequence ``tokens`` (S,): the mean
    cross-entropy of predicting tokens[1:] from tokens[:-1], plus the
    sequence's balance loss of every expert layer."""
    ein = make_einsum(matmul)
    eps = m["rms_norm_eps"]

    def make_layer(moe: bool):
        @jax.checkpoint
        def layer(h, lp, pos):
            h = h + mla(m, ein, rms_norm(h, lp["attn_norm"], eps), lp, pos)
            x = rms_norm(h, lp["mlp_norm"], eps)
            if moe:
                y, aux = expert_ffn(m, ein, x, lp)
            else:
                y = _swiglu(ein, x, lp["w_gate"], lp["w_up"], lp["w_down"])
                aux = jnp.zeros((), jnp.float32)
            return h + y, aux
        return layer

    runs = (("dense_layers", make_layer(False), DENSE_LEAVES),
            ("layers", make_layer(True), MOE_LEAVES))

    def loss(params, tokens):
        S = tokens.shape[0]
        pos = jnp.arange(S)
        h = params["embed"][tokens]
        aux = 0.0
        for group, layer, names in runs:
            for i in range(params[group]["wq"].shape[0]):
                h, a = layer(h, {n: params[group][n][i] for n in names}, pos)
                aux = aux + a
        h = rms_norm(h, params["final_norm"], eps)
        logits = ein("sd,dv->sv", h, params["unembed"])[:-1]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold) + aux

    return loss


# ---------------------------------------------------------------------------
# the step
# ---------------------------------------------------------------------------

def reference_steps(m: Mapping, opt: Mapping, seed: int,
                    grids: Sequence[np.ndarray], matmul: str = "f32",
                    rows: slice = slice(None)) -> Dict[str, object]:
    """Train ``len(grids)`` steps from the seed's weights, one grid
    (GB, S) per step, the gradient the mean over the grid's rows.

    Returns the loss of each step, the per-leaf norms of the first step's
    clipped gradient (``grad``) and of the parameters' change over all the
    steps (``change``), by leaf path. ``rows`` keeps only some rows of each
    grid: with half of them it is the half-batch fault.
    """
    init = jax.jit(make_init(m))
    grad_row = jax.jit(jax.value_and_grad(row_loss_fn(m, matmul)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    scale = jax.jit(lambda g, s: jax.tree_util.tree_map(lambda x: x * s, g),
                    donate_argnums=0)
    update = jax.jit(make_adamw(opt), donate_argnums=(0, 1, 2))
    words = seed_words(seed)
    params = init(words)
    paths = leaf_paths(params)
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    mom, var = zeros(params), zeros(params)
    losses: List[float] = []
    grad_norms = None
    for step, grid in enumerate(grids):
        grid = np.asarray(grid)[rows]
        total, gsum = 0.0, None
        for row in grid:
            loss, g = grad_row(params, jnp.asarray(row))
            total += float(loss)
            gsum = g if gsum is None else add(gsum, g)
            del g
        n = len(grid)
        norms = [float(x) / n for x in leaf_norms(gsum)]
        clip = min(1.0, opt["clip_norm"] / max(global_norm(norms), 1e-9)) \
            if opt["clip_norm"] > 0 else 1.0
        gsum = scale(gsum, np.float32(clip / n))
        if step == 0:
            grad_norms = [float(x) for x in leaf_norms(gsum)]
        params, mom, var = update(params, mom, var, gsum,
                                  np.float32(step),
                                  np.float32(learning_rate(opt, step)))
        del gsum
        losses.append(total / n)
    del mom, var
    change = change_norms(m)(params, words)
    return {"losses": losses, "grad": dict(zip(paths, grad_norms)),
            "change": dict(zip(paths, [float(x) for x in change]))}


def change_norms(m: Mapping) -> Callable:
    """jitted ``(params, seed_words) -> [norm of params - initial params]``
    per leaf, the initial draw made anew from the seed."""
    init = make_init(m)

    @jax.jit
    def f(params, words):
        p0 = init(words)
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))
                for a, b in zip(jax.tree_util.tree_leaves(params),
                                jax.tree_util.tree_leaves(p0))]
    return f


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------

def matmul_params(m: Mapping) -> float:
    """Parameters that take part in a matmul per token, a routed expert
    counted at the share of tokens that visit it: each of the held experts
    is one of a token's K choices of the router's E, K * held / E visits a
    token (0.75 for 6 of 64 with 8 held)."""
    D, H, r, dn, dr, dv = _dims(m)
    Fe = m["moe_intermediate_size"]
    attn = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    dense = 3 * D * m["intermediate_size"]
    visits = m["num_experts_per_tok"] * m["n_routed_experts"] \
        / m["router_experts"]
    moe = D * m["router_experts"] + 3 * D * Fe * (
        m["n_shared_experts"] + visits)
    n_dense = m["first_k_dense_replace"]
    n_moe = m["num_hidden_layers"] - n_dense
    return m["num_hidden_layers"] * attn + n_dense * dense + n_moe * moe \
        + D * m["vocab_size"]


def flops_per_token(m: Mapping, seq_len: int) -> float:
    """Model FLOPs per trained token, forward and backward, no recompute:
    6 per matmul parameter, plus the score and value products of attention
    over the whole square of scores, as ``bench/flops.py`` counts them (the
    step computes it dense): 2 * S * H * (dn + dr) for the scores and
    2 * S * H * dv for the values forward per layer, three times that with
    the backward."""
    _, H, _, dn, dr, dv = _dims(m)
    attn = 6 * m["num_hidden_layers"] * seq_len * H * (dn + dr + dv)
    return 6.0 * matmul_params(m) + attn
