"""chip_smoke.py's phases at smoke size on the CPU (kernels interpreted).

The script itself refuses to run off a TPU; these tests hand its training
and kernel phases a small configuration directly.
"""
import jax
import numpy as np
import pytest

from repro.configs import granite_8b
from repro.dataplane import Topology


@pytest.fixture(scope="module")
def small(chip_smoke):
    return chip_smoke.SmokeConfig(
        model=granite_8b.SMOKE_CONFIG,
        topology=Topology(dp=2, cp=1, global_batch=4, seq_len=64),
        microbatches=2)


def test_train_phase_replays_and_matches_the_sequential_read(chip_smoke,
                                                             small):
    r = chip_smoke.train_phase(small)
    n = (chip_smoke.WARMUP_STEPS + chip_smoke.TIMED_STEPS
         + chip_smoke.REPLAY_STEPS)
    assert len(r["losses"]) == n
    # every grid, the replayed ones included, equalled the sequential read
    assert r["grids_checked"] == n + chip_smoke.REPLAY_STEPS
    np.testing.assert_allclose(r["replay_losses"],
                               r["losses"][-chip_smoke.REPLAY_STEPS:],
                               rtol=chip_smoke.REPLAY_LOSS_RTOL)
    assert r["compiles_in_timed_steps"] == 0
    assert abs(r["losses"][0] - np.log(small.model.vocab_size)) \
        <= chip_smoke.FIRST_LOSS_BOUND


def test_train_phase_fails_on_a_grid_that_differs(chip_smoke, small,
                                                  monkeypatch):
    """The byte-identity check has teeth: one flipped token in the
    reference fails the phase."""
    read = chip_smoke.sequential_read

    def corrupted(store, topo, steps):
        grids = read(store, topo, steps)
        flipped = bytearray(grids[3])
        flipped[0] ^= 1
        grids[3] = bytes(flipped)
        return grids

    monkeypatch.setattr(chip_smoke, "sequential_read", corrupted)
    with pytest.raises(RuntimeError, match=r"steps \[3\] differ"):
        chip_smoke.train_phase(small)


def test_kernel_phase_matches_refs(chip_smoke):
    errors = chip_smoke.kernel_phase(chip_smoke.KernelShapes(
        flash=(1, 128, 4, 2, 64), decode=(2, 256, 8, 2, 64),
        rmsnorm=(2, 16, 128), wkv6=(1, 64, 2, 32)))
    assert set(errors) == {"flash_attention", "decode_attention", "rmsnorm",
                           "wkv6.y", "wkv6.state"}


def test_main_refuses_a_cpu_backend(chip_smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
