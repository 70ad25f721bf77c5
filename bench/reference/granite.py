"""Plain reference of the Granite decoder's training step: float32
``jax.numpy`` at ``highest`` matmul precision, written from the published
description and importing nothing of the program.

It also makes the benchmark's weights: ``init_params`` draws them from the
run's seed in one jitted call, in the layout the program's step takes
(``embed``, ``unembed``, ``final_norm``, and a ``layers`` dict whose leaves
stack the layers on a leading axis). Both the program and this reference
start from that draw.

The block (Granite 8B Code, arXiv:2405.04324, a Llama-style decoder):

    h = embed[tokens]
    per layer:  h += Wo . attn(rope(Wq . n1(h)), rope(Wk . n1(h)), Wv . n1(h))
                h += Wdown . (silu(Wgate . n2(h)) * (Wup . n2(h)))
    logits = Wunembed . n(h);  loss = mean next-token cross-entropy

with RMSNorm n(x) = x / sqrt(mean(x^2) + eps) * scale, grouped-query
attention (query head i reads key/value head i // (H / G)), causal softmax
over scores scaled by 1/sqrt(head_dim), and rotary embedding on the two
halves of each head (rotate-half). The step is AdamW with global-norm
clipping, as the configuration's ``optimizer`` states it.

Departures, each also the program's: the head is a separate matrix (no tied
embedding); the vocabulary is the configuration's slice, so logits and loss
run over the slice only; weight decay applies to every leaf stored with two
or more dimensions, which takes in the stacked per-layer norm scales.

``matmul`` selects the arithmetic of every product: ``"f32"`` is the
reference; ``"fp8"`` is the lower-precision control (operands rounded to
float8_e4m3fn, cotangents to float8_e5m2, each with a per-tensor scale,
products accumulated in float32).

Besides the reference, this module states how the benchmark runs the
program on Granite (``program_config``), what the model's FLOPs are
(``flops_per_token``, from ``bench/flops.py``) and the CPU tests' size
(``SMOKE``): the contract of ``bench/reference/__init__.py``. Only
``program_config`` imports the program, inside the function.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import flops_per_token  # noqa: F401  (the contract's)

LEAVES = ("embed", "unembed", "final_norm")
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")

#: the CPU tests' size: Granite's layout with tiny widths
SMOKE = {
    "model": dict(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=160,
                  vocab_size=257),
    # the smoke size's own limits, above what sound runs read on the CPU
    # (six seeds: loss 4.3e-4, gradient 4.8e-3, change 1.8e-3) and below
    # what the fp8 control read on three (1.3e-3, 2.7e-2, 3.8e-3)
    "limits": dict(loss_gap=1e-3, grad_gap=1.2e-2, change_gap=3e-3),
}


def program_config(cfg: Mapping):
    """The program's ``ModelConfig`` for a Granite configuration."""
    from repro.models import ModelConfig
    m, prec = cfg["model"], cfg["precision"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        tie_embeddings=m["tie_word_embeddings"],
        param_dtype=prec["params"], compute_dtype=prec["compute"])


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 64 bits as two uint32 words, an argument of the
    jitted draw (so one compiled program serves every seed)."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def shapes(m: Mapping) -> Dict:
    """Leaf shapes of the parameter tree, and the fan-in of each weight."""
    D, V, L = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    H, G, dh = m["num_attention_heads"], m["num_key_value_heads"], \
        m["head_dim"]
    F = m["intermediate_size"]
    return {
        "embed": (V, D), "unembed": (D, V), "final_norm": (D,),
        "layers": {"attn_norm": (L, D), "wq": (L, D, H, dh),
                   "wk": (L, D, G, dh), "wv": (L, D, G, dh),
                   "wo": (L, H, dh, D), "mlp_norm": (L, D),
                   "w_gate": (L, D, F), "w_up": (L, D, F),
                   "w_down": (L, F, D)}}


def _std(name: str, m: Mapping) -> float:
    """Init scale: 0.02 for the embedding, 1/sqrt(fan-in) for weights."""
    D, F = m["hidden_size"], m["intermediate_size"]
    hd = m["num_attention_heads"] * m["head_dim"]
    return {"embed": 0.02, "unembed": D ** -0.5, "wq": D ** -0.5,
            "wk": D ** -0.5, "wv": D ** -0.5, "wo": hd ** -0.5,
            "w_gate": D ** -0.5, "w_up": D ** -0.5, "w_down": F ** -0.5}[name]


def make_init(m: Mapping) -> Callable:
    """``init(seed_words) -> params``, float32, norm scales at one."""
    tree = shapes(m)

    def init(words):
        key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])

        def leaf(i, name, shape):
            if name.endswith("norm"):
                return jnp.ones(shape, jnp.float32)
            return jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * _std(name, m)

        out = {n: leaf(i, n, tree[n]) for i, n in enumerate(LEAVES)}
        out["layers"] = {n: leaf(len(LEAVES) + i, n, tree["layers"][n])
                         for i, n in enumerate(LAYER_LEAVES)}
        return out

    return init


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _scaled(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _fp8_einsum(spec: str):
    """einsum with both operands in e4m3 and the cotangent in e5m2."""
    lhs, out = spec.split("->")
    a_s, b_s = lhs.split(",")
    hp = jax.lax.Precision.HIGHEST

    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, _scaled(a, jnp.float8_e4m3fn),
                          _scaled(b, jnp.float8_e4m3fn), precision=hp)

    def fwd(a, b):
        qa = _scaled(a, jnp.float8_e4m3fn)
        qb = _scaled(b, jnp.float8_e4m3fn)
        return jnp.einsum(spec, qa, qb, precision=hp), (qa, qb)

    def bwd(res, ct):
        qa, qb = res
        ct = _scaled(ct, jnp.float8_e5m2)
        da = jnp.einsum(f"{out},{b_s}->{a_s}", ct, qb, precision=hp)
        db = jnp.einsum(f"{out},{a_s}->{b_s}", ct, qa, precision=hp)
        return da, db

    f.defvjp(fwd, bwd)
    return f


def make_einsum(matmul: str) -> Callable:
    if matmul == "f32":
        return lambda spec, a, b: jnp.einsum(
            spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if matmul == "fp8":
        cache: Dict[str, Callable] = {}

        def ein(spec, a, b):
            if spec not in cache:
                cache[spec] = _fp8_einsum(spec)
            return cache[spec](a, b)
        return ein
    raise ValueError(f"unknown matmul arithmetic {matmul!r}")


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotary embedding on (S, heads, dh), rotate-half convention."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def row_loss_fn(m: Mapping, matmul: str = "f32") -> Callable:
    """``loss(params, tokens)`` of one sequence ``tokens`` (S,): the mean
    cross-entropy of predicting tokens[1:] from tokens[:-1]."""
    ein = make_einsum(matmul)
    H, G = m["num_attention_heads"], m["num_key_value_heads"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    rep = H // G

    def attention(q, k, v):
        # q (S, G, rep, dh); k, v (S, G, dh); one key/value group at a time,
        # each recomputed in the backward pass, so scores of one group live
        S, dh = q.shape[0], q.shape[-1]
        causal = jnp.tril(jnp.ones((S, S), bool))

        @jax.checkpoint
        def group(args):
            qg, kg, vg = args                       # (S, rep, dh), (S, dh)
            s = ein("qrd,kd->rqk", qg, kg) / math.sqrt(dh)
            s = jnp.where(causal[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return ein("rqk,kd->qrd", p, vg)

        out = jax.lax.map(group, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                                  jnp.moveaxis(v, 1, 0)))
        return jnp.moveaxis(out, 0, 1)             # (S, G, rep, dh)

    @jax.checkpoint
    def layer(h, lp, pos):
        S = h.shape[0]
        x = rms_norm(h, lp["attn_norm"], eps)
        q = rope(ein("sd,dhk->shk", x, lp["wq"]), pos, theta)
        k = rope(ein("sd,dgk->sgk", x, lp["wk"]), pos, theta)
        v = ein("sd,dgk->sgk", x, lp["wv"])
        o = attention(q.reshape(S, G, rep, -1), k, v).reshape(S, H, -1)
        h = h + ein("shk,hkd->sd", o, lp["wo"])
        x = rms_norm(h, lp["mlp_norm"], eps)
        mid = jax.nn.silu(ein("sd,df->sf", x, lp["w_gate"])) \
            * ein("sd,df->sf", x, lp["w_up"])
        return h + ein("sf,fd->sd", mid, lp["w_down"])

    def loss(params, tokens):
        S = tokens.shape[0]
        pos = jnp.arange(S)
        h = params["embed"][tokens]
        for i in range(m["num_hidden_layers"]):
            h = layer(h, {n: params["layers"][n][i] for n in LAYER_LEAVES},
                      pos)
        h = rms_norm(h, params["final_norm"], eps)
        logits = ein("sd,dv->sv", h, params["unembed"])[:-1]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold)

    return loss


# ---------------------------------------------------------------------------
# the optimizer
# ---------------------------------------------------------------------------

def learning_rate(opt: Mapping, step: int) -> float:
    warm = min(1.0, (step + 1.0) / max(1, opt["warmup_steps"]))
    t = min(1.0, max(0.0, (step - opt["warmup_steps"])
                     / max(1, opt["total_steps"] - opt["warmup_steps"])))
    cos = 0.5 * (1.0 + math.cos(math.pi * t))
    frac = opt["min_lr_frac"] + (1.0 - opt["min_lr_frac"]) * cos
    return opt["learning_rate"] * warm * frac


def make_adamw(opt: Mapping) -> Callable:
    """``update(params, m, v, grads, step) -> (params, m, v)`` on clipped
    gradients, bias-corrected, decoupled weight decay."""
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]

    def update(params, m, v, grads, step, lr):
        t = step + 1.0
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

        def one(p, m_, v_, g):
            m_ = b1 * m_ + (1 - b1) * g
            v_ = b2 * v_ + (1 - b2) * g * g
            delta = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
            if p.ndim >= 2:
                delta = delta + wd * p
            return p - lr * delta, m_, v_

        out = jax.tree_util.tree_map(one, params, m, v, grads)
        pick = lambda i: jax.tree_util.tree_map(
            lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    return update


def leaf_paths(tree) -> List[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    """Euclidean norm of every leaf, in float32."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def global_norm(norms: Sequence[float]) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(norms, np.float64)))))


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
