"""DeepSeek-V2-Lite at one chip's share: the program's latent attention and
expert layer against the plain reference (``bench/reference/deepseek_v2``),
at smoke size on the CPU, both in float32 where they are compared."""
import copy
import json
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.harness import Run  # noqa: E402
from bench.reference import deepseek_v2 as ref  # noqa: E402
from bench.reference.dataplane import TokenGenerator  # noqa: E402
from bench.reference.granite import make_einsum  # noqa: E402
from bench.tests.smoke import smoke_config  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
FILE = ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8.json"
SEED = 2**35 + 17


def config() -> dict:
    return json.loads(FILE.read_text())


def smoke_f32() -> dict:
    cfg = smoke_config(config())
    cfg["precision"]["compute"] = "float32"
    return cfg


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_f32()
    t = cfg["train"]
    gen = TokenGenerator(SEED, cfg["model"]["vocab_size"], t["global_batch"],
                         t["seq_len"], 1.0)
    return cfg, [gen.grid(0, s) for s in range(3)]


def layer_params(params, group, i=0):
    return {n: a[i] for n, a in params[group].items()}


# -- the configuration ------------------------------------------------------

def test_the_file_states_the_published_config_as_run():
    cfg = config()
    m = cfg["model"]
    # the catalog's keys at the top level are the ones the harness runs
    for k, v in m.items():
        if k not in ("router_experts", "aux_loss_alpha"):
            assert cfg[k] == v, k
    assert m["router_experts"] == cfg["published"]["n_routed_experts"] == 64
    assert (m["num_hidden_layers"], m["n_routed_experts"],
            m["vocab_size"]) == (5, 8, 12800)
    assert set(cfg["reduced"]) == set(cfg["published"])
    mc = harness.model_config(cfg)
    assert (mc.d_model, mc.num_heads, mc.kv_lora_rank, mc.head_dim,
            mc.qk_rope_head_dim, mc.v_head_dim) == (2048, 16, 512, 128, 64,
                                                     128)
    assert (mc.moe_num_experts, mc.experts_held, mc.moe_top_k,
            mc.moe_num_shared, mc.moe_d_ff, mc.d_ff) == (64, 8, 6, 2, 1408,
                                                         10944)
    assert (mc.first_dense_layers, mc.moe_norm_topk) == (1, False)
    assert mc.param_count() == 535060992


def test_flops_per_token_by_hand():
    m = config()["model"]
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    dense = 3 * 2048 * 10944
    # router, shared experts whole, 8 held experts visited 6 * 8 / 64 times
    moe = 2048 * 64 + 3 * 2048 * 1408 * (2 + 0.75)
    params = 5 * attn + dense + 4 * moe + 2048 * 12800
    assert ref.matmul_params(m) == params == 257949696
    scores_values = 6 * 5 * 4096 * 16 * (192 + 128)
    assert ref.flops_per_token(m, 4096) == 6 * params + scores_values
    assert ref.flops_per_token(m, 4096) == pytest.approx(2.1768e9, rel=1e-4)


def test_yarn_frequencies_and_scale_agree_with_the_reference():
    from repro.models.common import rope_angles, yarn_inv_freq
    mc = harness.model_config(config())
    m = config()["model"]
    np.testing.assert_allclose(
        yarn_inv_freq(64, 10000.0, mc.rope_yarn), ref.yarn_inv_freq(m),
        rtol=1e-6)
    # the published settings interpolate: the slowest pairs turn 40x slower
    inv = ref.yarn_inv_freq(m)
    assert inv[0] == 1.0 and inv[-1] == pytest.approx(
        10000.0 ** (-62 / 64) / 40, rel=1e-6)
    cos, _ = rope_angles(jnp.arange(3), 64, 10000.0, mc.rope_yarn)
    np.testing.assert_allclose(cos[0], 1.0)     # mscale == mscale_all_dim
    from repro.models.transformer import mla_scale
    mscale = 0.1 * 0.707 * np.log(40) + 1
    assert mla_scale(mc) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert ref.attention_scale(m) == pytest.approx(mla_scale(mc))


# -- the program against the reference -------------------------------------

def test_mla_forward_matches_the_reference(setup):
    from repro.models.transformer import mla_block, rope_table
    cfg, grids = setup
    m = cfg["model"]
    mc = harness.model_config(cfg)
    params = jax.jit(ref.make_init(m))(ref.seed_words(SEED))
    lp = layer_params(params, "layers")
    S = grids[0].shape[1]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, m["hidden_size"]))
    cos, sin = rope_table(mc, jnp.arange(S))
    got = mla_block(mc, lp, x, cos[None], sin[None])
    ein = make_einsum("f32")
    want = jnp.stack([ref.mla(m, ein, row, lp, jnp.arange(S)) for row in x])
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_loss_and_gradient_match_the_program(setup):
    from repro.models import model as M
    cfg, grids = setup
    m = cfg["model"]
    params = jax.jit(ref.make_init(m))(ref.seed_words(SEED))
    prog = jax.value_and_grad(
        lambda p, t: M.loss_fn(harness.model_config(cfg), p, {"tokens": t})[0])
    loss_p, grad_p = prog(params, jnp.asarray(grids[0]))
    row = jax.value_and_grad(ref.row_loss_fn(m))
    outs = [row(params, jnp.asarray(r)) for r in grids[0]]
    np.testing.assert_allclose(float(loss_p),
                               np.mean([float(l) for l, _ in outs]), rtol=1e-5)
    grad_r = jax.tree_util.tree_map(lambda *g: sum(g) / len(g),
                                    *[g for _, g in outs])
    for path, a, b in zip(ref.leaf_paths(params),
                          jax.tree_util.tree_leaves(grad_p),
                          jax.tree_util.tree_leaves(grad_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=1e-5 * float(jnp.abs(b).max()),
                                   err_msg=path)


def program_steps(cfg, grids):
    """The program's readings of ``len(grids)`` steps, as the harness
    takes them: each loss, the first clipped gradient's norms and the
    change's norms, by leaf."""
    step = harness.make_step(cfg)
    params, opt = harness.make_state(cfg, SEED)
    paths = ref.leaf_paths(params)
    prog = {"losses": []}
    for i, g in enumerate(grids):
        params, opt, metrics = step(params, opt, {"tokens": jnp.asarray(g)})
        prog["losses"].append(float(metrics["loss"]))
        if i == 0:
            prog["grad"] = dict(zip(paths, [
                float(x) / (1 - cfg["optimizer"]["b1"])
                for x in ref.leaf_norms(opt["m"])]))
    prog["change"] = dict(zip(paths, [float(x) for x in ref.change_norms(
        cfg["model"])(params, ref.seed_words(SEED))]))
    return prog


def test_three_steps_match_the_program_step(setup):
    cfg, grids = setup
    want = ref.reference_steps(cfg["model"], cfg["optimizer"], SEED, grids)
    got = harness.compare_steps(program_steps(cfg, grids), want,
                                cfg["limits"])
    for name, (value, _) in got.items():
        assert value < 1e-4, (name, value)


# -- the expert layer at a share --------------------------------------------

def _layer(cfg, held):
    """The program's config, holding ``held`` experts from expert 0."""
    return harness.model_config(cfg).replace(moe_experts_held=held)


def test_the_shares_sum_to_the_uncut_layer():
    """A layer of 8 experts split into 4 shares of 2, as 4 chips of expert
    parallelism hold it: the shares' routed parts, with the shared experts
    that every chip computes alike counted once, are the uncut layer."""
    from repro.models.moe import route, routed_experts
    cfg = smoke_f32()
    m = dict(cfg["model"], router_experts=8, n_routed_experts=8)
    cfg["model"] = m
    mc = harness.model_config(cfg)
    params = jax.jit(ref.make_init(m))(ref.seed_words(SEED))
    lp = layer_params(params, "layers")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, m["hidden_size"]))
    gates, idx, _ = route(mc, lp["router"], x)
    flat = lambda a: a.reshape(32, -1)
    total, rows = 0.0, 0
    for e0 in (0, 2, 4, 6):
        y, r = routed_experts(mc, flat(x), flat(gates), flat(idx),
                              lp["w_gate"][e0:e0 + 2], lp["w_up"][e0:e0 + 2],
                              lp["w_down"][e0:e0 + 2], e0)
        total, rows = total + y, rows + int(jnp.sum(r))
    ein = make_einsum("f32")
    shared = [ref.expert_ffn(dict(m, n_routed_experts=0), ein, r, lp)[0]
              for r in x]
    total = total.reshape(x.shape) + jnp.stack(shared)
    want = jnp.stack([ref.expert_ffn(m, ein, r, lp)[0] for r in x])
    np.testing.assert_allclose(total, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert rows == 2 * 16 * m["num_experts_per_tok"]


@pytest.mark.parametrize("top_k", [1, 2])
def test_no_choice_of_a_held_expert_is_dropped(top_k):
    """A router that sends every token's choices to the held experts, the
    worst imbalance: all of them are computed and agree with the reference."""
    from repro.models.moe import moe_ffn
    cfg = smoke_f32()
    m = dict(cfg["model"], num_experts_per_tok=top_k)
    cfg["model"] = m
    params = jax.jit(ref.make_init(m))(ref.seed_words(SEED))
    lp = layer_params(params, "layers")
    D, E = m["hidden_size"], m["router_experts"]
    # positive tokens, and router columns +10 for expert 0, +5 for expert 1
    # (the held ones), -10 for the others: expert 0 first, then expert 1
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 32, D))) + 0.1
    lp["router"] = jnp.ones((D, E)) * jnp.array([10.0, 5.0] + [-10.0] * (E - 2))
    y, _, counts = moe_ffn(_layer(cfg, 2), lp, x)
    T = 2 * 32
    assert int(counts["moe_rows_local"]) == T * top_k
    assert int(counts["moe_rows_max"]) == T
    want = jnp.stack([ref.expert_ffn(m, make_einsum("f32"), r, lp)[0]
                      for r in x])
    np.testing.assert_allclose(y, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_the_step_counts_the_rows_it_computed(setup):
    cfg, grids = setup
    params, opt = harness.make_state(cfg, SEED)
    metrics = harness.make_step(cfg)(params, opt,
                                     {"tokens": jnp.asarray(grids[0])})[2]
    m = cfg["model"]
    T = grids[0].size
    rows, fullest = int(metrics["moe_rows_local"]), int(metrics["moe_rows_max"])
    # 2 held of 4, top 2: about half of the T * K choices, one expert layer
    assert 0 < rows <= T * m["num_experts_per_tok"]
    # the fullest expert of one microbatch holds at most that microbatch's
    # tokens, and at least its share of the rows
    micro = cfg["train"]["microbatches"]
    assert rows / (micro * m["n_routed_experts"]) <= fullest <= T // micro


# -- the per-layer reader -----------------------------------------------------

def test_load_max_ratio_is_the_median_of_fullest_over_mean():
    cfg = config()
    groups = 8 * 4 * cfg["train"]["microbatches"]

    def sync(step, local, fullest):
        return SimpleNamespace(name="pipeline.sync", args={
            "step": step, "moe_rows_local": local, "moe_rows_max": fullest})

    spans = [sync(0, 6144 * groups, 9000), sync(1, 6144 * groups, 7000),
             sync(2, 6144 * groups, 8000),
             SimpleNamespace(name="pipeline.dispatch", args={"step": 0})]
    got = bench_run.read_metric("moe.load_max_ratio",
                                Run(config=cfg, spans=spans))
    assert got == pytest.approx(8000 / 6144)
    granite = json.loads(
        (ROOT / "bench/configs/granite8b-pretrain.json").read_text())
    no_counts = [SimpleNamespace(name="pipeline.sync", args={"step": 0})]
    for run in (Run(config=cfg, spans=no_counts),
                Run(config=granite, spans=spans)):
        assert bench_run.read_metric("moe.load_max_ratio", run) is None


def test_smoke_size_limits_stand_between_sound_runs_and_the_control(setup):
    """The smoke limits lie between what the program reads and what the
    fp8 control reads, on this seed."""
    cfg, grids = setup
    cfg = copy.deepcopy(cfg)
    cfg["precision"]["compute"] = "bfloat16"
    prog = program_steps(cfg, grids)
    want = ref.reference_steps(cfg["model"], cfg["optimizer"], SEED, grids)
    fp8 = ref.reference_steps(cfg["model"], cfg["optimizer"], SEED, grids,
                              matmul="fp8")
    limits = cfg["limits"]
    sound = harness.compare_steps(prog, want, limits)
    control = harness.compare_steps(fp8, want, limits)
    assert all(v <= lim for v, lim in sound.values()), sound
    assert any(v > lim for v, lim in control.values()), control


# -- Granite beside it ---------------------------------------------------------

def test_granite_seeded_loss_and_step_are_unchanged():
    """The smoke size's loss and first step's metrics on one seed, as the
    program read them before the expert layer and the layer runs: Granite's
    path computes what it computed."""
    from bench.reference import granite
    from bench.tests.smoke import smoke_cell
    from repro.models import model as M
    cfg = smoke_cell("granite8b-pretrain", "steady").config
    seed = 2**34 + 3
    params = jax.jit(granite.make_init(cfg["model"]))(granite.seed_words(seed))
    tokens = TokenGenerator(seed, cfg["model"]["vocab_size"], 4, 64,
                            1.0).grid(0, 0)
    loss, metrics = M.loss_fn(harness.model_config(cfg), params,
                              {"tokens": tokens})
    assert float(loss) == 6.197622299194336
    assert sorted(metrics) == ["aux_loss", "ce_loss"]
    params, opt = harness.make_state(cfg, seed)
    metrics = harness.make_step(cfg)(params, opt, {"tokens": tokens})[2]
    assert float(metrics["loss"]) == 6.197597503662109
    assert sorted(metrics) == ["aux_loss", "grad_norm", "loss", "lr"]
