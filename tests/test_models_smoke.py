"""Per-architecture smoke tests: reduced config, one forward/train step on CPU,
shape + finiteness asserts (assignment requirement f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.models import (init_decode_state, init_params, loss_fn, forward,
                          decode_step, param_specs)
from repro.train.optimizer import OptimizerConfig
from repro.train.step import StepConfig, make_train_step
from repro.train.optimizer import init_opt_state

B, S = 2, 24


def _batch(cfg):
    rng = np.random.default_rng(0)
    if cfg.family == "audio":
        tokens = rng.integers(0, cfg.vocab_size,
                              (B, S, cfg.num_codebooks)).astype(np.int32)
    else:
        tokens = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    batch = {"tokens": jnp.asarray(tokens)}
    if cfg.frontend != "none":
        batch["frontend_embeds"] = jnp.full((B, 4, cfg.d_model), 0.01,
                                            jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    params = init_params(param_specs(cfg), seed=0)
    batch = _batch(cfg)
    logits, aux = forward(cfg, params, batch)
    P = 4 if cfg.frontend != "none" else 0
    if cfg.family == "audio":
        assert logits.shape == (B, S + P, cfg.num_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S + P, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_one_train_step(arch):
    cfg = get_smoke_config(arch)
    params = init_params(param_specs(cfg), seed=0)
    opt = init_opt_state(params)
    step_fn = jax.jit(make_train_step(
        cfg, OptimizerConfig(learning_rate=1e-3, warmup_steps=1,
                             total_steps=10),
        StepConfig(microbatches=2)))
    new_params, new_opt, metrics = step_fn(params, opt, _batch(cfg))
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    assert int(new_opt["step"]) == 1
    # parameters actually moved
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        params, new_params)
    assert max(jax.tree_util.tree_leaves(moved)) > 0


@pytest.mark.parametrize("arch", ["granite_8b", "rwkv6_3b", "zamba2_7b",
                                  "deepseek_moe_16b", "musicgen_medium"])
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    params = init_params(param_specs(cfg), seed=0)
    st = init_decode_state(cfg, B, 16)
    if cfg.family == "audio":
        tok = jnp.zeros((B, cfg.num_codebooks), jnp.int32)
    else:
        tok = jnp.zeros((B,), jnp.int32)
    logits, st2 = decode_step(cfg, params, st, tok, jnp.int32(0))
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # state structure preserved
    assert set(st2.keys()) == set(st.keys())


@pytest.mark.parametrize("arch", ["granite_8b", "rwkv6_3b", "zamba2_7b"])
def test_decode_matches_forward_fp32(arch):
    cfg = get_smoke_config(arch).replace(compute_dtype="float32")
    params = init_params(param_specs(cfg), seed=1)
    S_ = 10
    tokens = (jnp.arange(B * S_).reshape(B, S_) * 5 % cfg.vocab_size
              ).astype(jnp.int32)
    lf, _ = forward(cfg, params, {"tokens": tokens})
    st = init_decode_state(cfg, B, S_)
    errs = []
    for t in range(S_):
        lg, st = decode_step(cfg, params, st, tokens[:, t], jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg - lf[:, t]))))
    assert max(errs) < 1e-4, errs


def test_moe_decode_matches_forward_when_capacity_unbounded():
    """The expert layer has no capacity: it drops no token in the forward
    pass or in single-token decode, so the two paths agree exactly (the
    leading dense layer included)."""
    cfg = get_smoke_config("deepseek_moe_16b").replace(
        compute_dtype="float32")
    params = init_params(param_specs(cfg), seed=0)
    S_ = 10
    tokens = (jnp.arange(B * S_).reshape(B, S_) * 3 % cfg.vocab_size
              ).astype(jnp.int32)
    lf, _ = forward(cfg, params, {"tokens": tokens})
    st = init_decode_state(cfg, B, S_)
    errs = []
    for t in range(S_):
        lg, st = decode_step(cfg, params, st, tokens[:, t], jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg - lf[:, t]))))
    assert max(errs) < 1e-4


def test_decode_cache_modes_agree():
    """readonly_fused (Perf iteration) must match the scan_carry baseline."""
    base = get_smoke_config("granite_8b").replace(compute_dtype="float32")
    params = init_params(param_specs(base), seed=2)
    S_ = 8
    tokens = (jnp.arange(B * S_).reshape(B, S_) * 7 % base.vocab_size
              ).astype(jnp.int32)
    outs = {}
    for mode in ("scan_carry", "readonly_fused"):
        cfg = base.replace(decode_cache_mode=mode)
        st = init_decode_state(cfg, B, S_)
        logits = []
        for t in range(S_):
            lg, st = decode_step(cfg, params, st, tokens[:, t], jnp.int32(t))
            logits.append(lg)
        outs[mode] = jnp.stack(logits)
    err = float(jnp.max(jnp.abs(outs["scan_carry"] - outs["readonly_fused"])))
    assert err < 1e-4, err


def test_full_configs_match_assignment():
    """The FULL configs carry the exact assigned hyperparameters."""
    expect = {
        "rwkv6_3b": (32, 2560, 8960, 65536),
        "qwen15_32b": (64, 5120, 27392, 152064),
        "llama3_405b": (126, 16384, 53248, 128256),
        "granite_8b": (36, 4096, 14336, 49152),
        "deepseek_67b": (95, 8192, 22016, 102400),
        "deepseek_moe_16b": (28, 2048, 10944, 102400),
        "qwen3_moe_235b_a22b": (94, 4096, 1536, 151936),
        "zamba2_7b": (81, 3584, 14336, 32000),
        "internvl2_76b": (80, 8192, 28672, 128256),
        "musicgen_medium": (48, 1536, 6144, 2048),
    }
    for arch, (L, D, F, V) in expect.items():
        cfg = get_config(arch)
        assert cfg.num_layers == L and cfg.d_model == D
        assert cfg.d_ff == F and cfg.vocab_size == V
    # GQA + family details
    assert get_config("llama3_405b").num_kv_heads == 8
    assert get_config("qwen15_32b").qkv_bias
    assert get_config("deepseek_moe_16b").moe_num_shared == 2
    assert get_config("deepseek_moe_16b").moe_top_k == 6
    assert get_config("deepseek_moe_16b").moe_d_ff == 1408
    assert get_config("deepseek_moe_16b").first_dense_layers == 1
    assert not get_config("deepseek_moe_16b").moe_norm_topk
    assert get_config("qwen3_moe_235b_a22b").moe_num_experts == 128
    assert get_config("zamba2_7b").ssm_state == 64
    assert get_config("musicgen_medium").num_codebooks == 4


def test_param_counts_match_nominal_sizes():
    tol = {
        "rwkv6_3b": (2.5e9, 3.5e9),
        "llama3_405b": (395e9, 415e9),
        "deepseek_67b": (60e9, 70e9),
        "deepseek_moe_16b": (15e9, 18e9),
        "qwen3_moe_235b_a22b": (225e9, 245e9),
        "zamba2_7b": (6e9, 8.5e9),
    }
    for arch, (lo, hi) in tol.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)
    a22 = get_config("qwen3_moe_235b_a22b").active_param_count()
    assert 20e9 <= a22 <= 24e9
    # DeepSeekMoE 16B: 2.8B active, its dense first layer whole
    a28 = get_config("deepseek_moe_16b").active_param_count()
    assert 2.7e9 <= a28 <= 2.9e9


def test_active_params_count_held_experts_at_their_visits():
    """A chip holding 8 of 64 experts: each held expert is visited by
    6 * 8 / 64 = 0.75 of a token's choices; the dense layer counts whole."""
    full = get_config("deepseek_moe_16b")
    cut = full.replace(num_layers=5, moe_experts_held=8)
    per_expert = 3 * 2048 * 1408
    assert cut.param_count() - cut.active_param_count() \
        == 4 * (8 - 0.75) * per_expert
    assert full.param_count() - full.active_param_count() \
        == 27 * (64 - 6) * per_expert


@pytest.mark.parametrize("impl", ["dense", "chunked"])
def test_attention_takes_a_scale_and_a_v_width_of_its_own(impl):
    """q.k width 24, v width 16, scale 0.3: against the softmax written out;
    and without a scale, 1/sqrt(q.k width) as before."""
    from repro.models.common import attention
    B, S, H, dh, dv = 2, 12, 3, 24, 16
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, d))
               for i, d in enumerate((dh, dh, dv)))
    mask = np.tril(np.ones((S, S), bool))
    for scale in (0.3, None):
        s = jnp.einsum("bshd,bthd->bhst", q, k) * (scale or dh ** -0.5)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhst,bthd->bshd", p, v)
        got = attention(q, k, v, impl=impl, chunk=4, scale=scale)
        assert got.shape == (B, S, H, dv)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


MESH_CHECK = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs.registry import get_smoke_config
from repro.models import init_params, param_specs
from repro.models.moe import moe_ffn
from repro.sharding.specs import make_rules, use_rules
cfg = get_smoke_config("deepseek_moe_16b").replace(compute_dtype="float32")
lp = {k: v[0] for k, v in init_params(param_specs(cfg), 0)["layers"].items()}
x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, cfg.d_model))
y0, aux0, c0 = moe_ffn(cfg, lp, x)
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
with use_rules(make_rules(mesh, cfg.num_heads, cfg.num_kv_heads)), mesh:
    y1, aux1, c1 = jax.jit(lambda lp, x: moe_ffn(cfg, lp, x))(lp, x)
np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)
np.testing.assert_allclose(aux1, aux0, rtol=1e-5)
assert int(c1["moe_rows_local"]) == int(c0["moe_rows_local"]) == 4 * 16 * 2
print("ok")
"""


def test_expert_layer_on_a_mesh_matches_one_device():
    """Under a (data 2, model 2) mesh each model shard runs the same
    dropless body on its 4 experts: the psum of the shards' parts is the
    single-device layer. Four host devices, so in a process of its own."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", MESH_CHECK], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
