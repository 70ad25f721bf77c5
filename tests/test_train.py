"""Training substrate: optimizer math, microbatch equivalence, checkpointing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core import ManifestStore, MemoryObjectStore, Namespace, Producer
from repro.core.lifecycle import read_watermarks
from repro.models import init_params, param_specs
from repro.train.checkpoint import (list_checkpoints, restore_checkpoint,
                                    save_checkpoint)
from repro.train.optimizer import (OptimizerConfig, adamw_update, global_norm,
                                   init_opt_state, lr_at)
from repro.train.step import StepConfig, make_train_step


def test_adamw_first_step_math():
    cfg = OptimizerConfig(learning_rate=0.1, warmup_steps=1, total_steps=100,
                          weight_decay=0.0, clip_norm=0.0, schedule="constant")
    params = {"w": jnp.array([[1.0, 2.0]])}
    grads = {"w": jnp.array([[0.5, -0.5]])}
    opt = init_opt_state(params)
    new_p, new_opt, metrics = adamw_update(cfg, params, grads, opt)
    # bias-corrected first step: mhat = g, vhat = g^2 -> delta = sign(g)
    expected = params["w"] - 0.1 * jnp.sign(grads["w"])
    np.testing.assert_allclose(np.asarray(new_p["w"]), np.asarray(expected),
                               atol=1e-5)
    assert int(new_opt["step"]) == 1


def test_grad_clip_bounds_update():
    cfg = OptimizerConfig(learning_rate=0.1, clip_norm=1.0, warmup_steps=1,
                          weight_decay=0.0, schedule="constant")
    params = {"w": jnp.zeros((4,))}
    grads = {"w": jnp.full((4,), 100.0)}
    opt = init_opt_state(params)
    _p, _o, metrics = adamw_update(cfg, params, grads, opt)
    assert float(metrics["grad_norm"]) == pytest.approx(200.0)


def test_lr_schedule_warmup_and_cosine():
    cfg = OptimizerConfig(learning_rate=1.0, warmup_steps=10, total_steps=110,
                          min_lr_frac=0.1)
    assert float(lr_at(cfg, jnp.int32(0))) == pytest.approx(0.1)
    assert float(lr_at(cfg, jnp.int32(9))) == pytest.approx(1.0)
    end = float(lr_at(cfg, jnp.int32(110)))
    assert end == pytest.approx(0.1, abs=1e-2)


def test_microbatch_accumulation_equivalent():
    """n_micro=1 vs n_micro=4 produce (nearly) identical updates in fp32."""
    cfg = get_smoke_config("granite_8b").replace(compute_dtype="float32")
    params = init_params(param_specs(cfg), seed=0)
    tokens = (jnp.arange(4 * 16).reshape(4, 16) % cfg.vocab_size
              ).astype(jnp.int32)
    batch = {"tokens": tokens}
    opt_cfg = OptimizerConfig(learning_rate=1e-2, warmup_steps=1,
                              schedule="constant", clip_norm=0.0,
                              weight_decay=0.0)
    outs = {}
    for n in (1, 4):
        step = jax.jit(make_train_step(cfg, opt_cfg, StepConfig(microbatches=n)))
        p, o, m = step(params, init_opt_state(params), batch)
        outs[n] = (p, float(m["loss"]))
    assert outs[1][1] == pytest.approx(outs[4][1], rel=1e-5)
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), outs[1][0], outs[4][0])
    # fp32 accumulation-order differences pass through Adam's 1/sqrt(v)
    # normalization, so post-update params can differ by a few 1e-4 even when
    # the grads match to fp32 roundoff; 5e-4 still catches real accumulation
    # bugs (which show up at the 1e-2 learning-rate scale)
    assert max(jax.tree_util.tree_leaves(diffs)) < 5e-4


def test_loss_decreases_on_learnable_data():
    cfg = get_smoke_config("granite_8b")
    params = init_params(param_specs(cfg), seed=0)
    opt = init_opt_state(params)
    # successor sequences are learnable
    base = jnp.arange(16)[None, :] + jnp.arange(4)[:, None] * 3
    batch = {"tokens": (base % cfg.vocab_size).astype(jnp.int32)}
    step = jax.jit(make_train_step(
        cfg, OptimizerConfig(learning_rate=3e-3, warmup_steps=5,
                             total_steps=100), StepConfig(microbatches=1)))
    losses = []
    for _ in range(30):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_checkpoint_roundtrip_and_watermarks(ns):
    state = {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                   "b": jnp.ones((3,), jnp.bfloat16)},
        "opt": {"step": jnp.int32(7)},
    }
    save_checkpoint(ns, step=7, state=state, cursor=(12, 34),
                    consumer_ranks=[0, 1])
    assert list_checkpoints(ns) == [7]
    template = jax.tree_util.tree_map(jnp.zeros_like, state)
    restored, cursor, step = restore_checkpoint(ns, template)
    assert cursor == (12, 34) and step == 7
    for a, b in zip(jax.tree_util.tree_leaves(restored),
                    jax.tree_util.tree_leaves(state)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    wms = read_watermarks(ns)
    assert wms[0].version == 12 and wms[0].step == 34
    assert 1 in wms


def test_checkpoint_restore_specific_step(ns):
    for s in (5, 10):
        save_checkpoint(ns, step=s, state={"x": jnp.float32(s)},
                        cursor=(s, s))
    restored, cursor, step = restore_checkpoint(ns, {"x": jnp.float32(0)},
                                                step=5)
    assert float(restored["x"]) == 5.0 and step == 5


#: the concurrent restore's cases, each beside a 160-byte float32 leaf so the
#: state is over the (64-byte) pool threshold
_RESTORE_CASES = {
    "ragged": lambda: jnp.arange(7 * 13, dtype=jnp.float32).reshape(7, 13),
    "bfloat16": lambda: (jnp.arange(100) / 7).astype(jnp.bfloat16),
    "scalar": lambda: {"opt": {"step": jnp.int32(7)}},
    "under_the_threshold": lambda: jnp.ones(3, jnp.float32) / 3,
}


@pytest.fixture
def pool64(monkeypatch):
    from repro.train import checkpoint as ckpt

    monkeypatch.setattr(ckpt, "POOL_MIN_BYTES", 64)
    return ckpt


@pytest.mark.parametrize("case", sorted(_RESTORE_CASES))
def test_concurrent_restore_is_bit_exact(ns, store, pool64, case):
    state = {"case": _RESTORE_CASES[case](),
             "pad": jnp.arange(40, dtype=jnp.float32) * 1.1}
    key = pool64.upload_model_state(ns, 3, state)
    template = jax.tree_util.tree_map(jnp.zeros_like, state)
    store.stats.gets = store.stats.range_gets = 0
    back, doc = pool64.load_model_state(ns, key, template)
    assert doc["step"] == 3
    leaves, want = jax.tree_util.tree_leaves(back), \
        jax.tree_util.tree_leaves(state)
    for a, b in zip(leaves, want):
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    # the MANIFEST, then one whole GET per leaf
    assert store.stats.gets == 1 + len(want)
    assert store.stats.range_gets == 0


@pytest.mark.parametrize("nth", [1, 2])
def test_concurrent_restore_raises_on_a_failed_get(ns, store, pool64, nth):
    from repro.core import FaultInjector, InjectedCrash

    state = {"big": jnp.arange(100, dtype=jnp.float32),
             "small": jnp.ones(4, jnp.int32)}
    key = pool64.upload_model_state(ns, 1, state)
    store.faults = FaultInjector()
    store.faults.crash_on("get", "leaf-", nth=nth)
    template = jax.tree_util.tree_map(jnp.zeros_like, state)
    with pytest.raises(InjectedCrash):
        pool64.load_model_state(ns, key, template)
    store.faults = None
    back, _doc = pool64.load_model_state(ns, key, template)
    assert np.asarray(back["big"]).tobytes() == \
        np.asarray(state["big"]).tobytes()
