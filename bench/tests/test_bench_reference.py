"""The plain reference against the program's model and step, at smoke size
on the CPU, both in float32: they must agree to float32 rounding."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness  # noqa: E402
from bench.reference import granite as ref  # noqa: E402
from bench.reference.dataplane import TokenGenerator  # noqa: E402
from bench.tests.smoke import smoke_cell  # noqa: E402

SEED = 2**35 + 11


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_cell("granite8b-pretrain", "steady").config
    cfg["precision"]["compute"] = "float32"
    t = cfg["train"]
    gen = TokenGenerator(SEED, cfg["model"]["vocab_size"], t["global_batch"],
                         t["seq_len"], 1.0)
    return cfg, [gen.grid(0, s) for s in range(3)]


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
    loss_r = np.mean([float(l) for l, _ in outs])
    grad_r = jax.tree_util.tree_map(lambda *g: sum(g) / len(g),
                                    *[g for _, g in outs])
    np.testing.assert_allclose(float(loss_p), loss_r, rtol=1e-5)
    for path, a, b in zip(ref.leaf_paths(params),
                          jax.tree_util.tree_leaves(grad_p),
                          jax.tree_util.tree_leaves(grad_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=1e-6 * float(jnp.abs(b).max()),
                                   err_msg=path)


def test_three_steps_match_the_program_step(setup):
    cfg, grids = setup
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
    want = ref.reference_steps(cfg["model"], cfg["optimizer"], SEED, grids)
    got = harness.compare_steps(prog, want, cfg["limits"])
    for name, (value, _) in got.items():
        assert value < 1e-4, (name, value)


def test_fp8_control_departs_further_than_bf16(setup):
    cfg, grids = setup
    m = cfg["model"]
    params = jax.jit(ref.make_init(m))(ref.seed_words(SEED))
    tokens = jnp.asarray(grids[0][0])
    exact = float(ref.row_loss_fn(m)(params, tokens))
    fp8 = float(ref.row_loss_fn(m, "fp8")(params, tokens))
    cfg16 = dict(cfg, precision=dict(cfg["precision"], compute="bfloat16"))
    from repro.models import model as M
    bf16 = float(M.loss_fn(harness.model_config(cfg16), params,
                           {"tokens": tokens[None]})[0])
    assert abs(fp8 - exact) > 3 * abs(bf16 - exact)
