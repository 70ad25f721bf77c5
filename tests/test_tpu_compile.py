"""Compile rehearsals for one described TPU v5e chip, at real widths.

Nothing runs: the TPU compiler that ships with libtpu compiles for a chip
that is described, not attached, and refuses what the chip would refuse —
block shapes Mosaic cannot tile, a step that does not fit 16 GB of HBM. The
topology is described inside a fixture, never at import: one process at a
time may load libtpu.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.wkv6 import wkv6_fwd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # libtpu writes no logs
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_cases(shapes, sds):
    bf16 = jnp.bfloat16
    B, S, H, G, dh = shapes.flash
    yield "flash_attention", (
        lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False),
        sds((B, S, H, dh), bf16), sds((B, S, G, dh), bf16),
        sds((B, S, G, dh), bf16))
    B, T, H, G, dh = shapes.decode
    yield "decode_attention", (
        lambda q, k, v: decode_attention_fwd(q, k, v, T - 1,
                                             interpret=False),
        sds((B, H, dh), bf16), sds((B, T, G, dh), bf16),
        sds((B, T, G, dh), bf16))
    yield "rmsnorm", (
        lambda x, w: rmsnorm_fwd(x, w, interpret=False),
        sds(shapes.rmsnorm, bf16), sds(shapes.rmsnorm[-1:], jnp.float32))
    B, S, H, dh = shapes.wkv6
    yield "wkv6", (
        lambda r, k, v, w, u: wkv6_fwd(r, k, v, w, u, interpret=False),
        *(sds((B, S, H, dh), bf16) for _ in range(3)),
        sds((B, S, H, dh), jnp.float32), sds((H, dh), jnp.float32))


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "rmsnorm", "wkv6"])
def test_kernel_compiles_for_v5e(chip_smoke, one_chip, name):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn, *shapes = dict(_kernel_cases(chip_smoke.CHIP_KERNELS, sds))[name]
    assert "tpu_custom_call" in _compile(fn, *shapes).as_text()


def test_chip_smoke_train_step_fits_one_v5e(chip_smoke, one_chip):
    """The step chip_smoke.py trains, at its size and with its donation: the
    compile raises RESOURCE_EXHAUSTED if it does not fit in HBM."""
    sc = chip_smoke.CHIP
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    state = on_chip(chip_smoke.abstract_state(sc.model))
    tokens = jax.ShapeDtypeStruct(
        (sc.topology.global_batch, sc.topology.seq_len), jnp.int32,
        sharding=one_chip)
    compiled = chip_smoke.make_step(sc).lower(
        state["params"], state["opt"], {"tokens": tokens}).compile()
    mem = compiled.memory_analysis()
    # params and Adam state are donated: the outputs reuse their buffers
    assert mem.alias_size_in_bytes >= 0.99 * mem.argument_size_in_bytes
