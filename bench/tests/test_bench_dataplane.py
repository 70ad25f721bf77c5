"""The exactly-once and byte check of consumed grids: it passes the stream
as committed and fails a flipped byte, a dropped TGB and a doubled TGB."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import pytest  # noqa: E402

from bench.reference.dataplane import TokenGenerator, check_consumed  # noqa: E402

WRITTEN = {0: 5, 1: 5}


@pytest.fixture(scope="module")
def gen():
    return TokenGenerator(2**40 + 3, 257, 4, 32, 1.0)


def stream(gen):
    """Two producers' TGBs interleaved as a commit race might order them."""
    order = [(0, 0), (1, 0), (1, 1), (0, 1), (0, 2), (1, 2), (1, 3), (0, 3)]
    return [gen.grid(p, s).tobytes() for p, s in order], order


def wrong(result):
    return result["unknown"] + result["duplicated"] + result["out_of_order"]


def test_generator_is_a_function_of_the_seed(gen):
    again = TokenGenerator(2**40 + 3, 257, 4, 32, 1.0)
    other = TokenGenerator(2**40 + 4, 257, 4, 32, 1.0)
    g = gen.grid(1, 3)
    assert g.shape == (4, 32) and g.dtype.name == "int32"
    assert (g == again.grid(1, 3)).all()
    assert not (g == other.grid(1, 3)).all()
    assert 0 <= g.min() and g.max() < 257


def test_committed_stream_passes(gen):
    grids, order = stream(gen)
    result = check_consumed(gen, grids, WRITTEN)
    assert wrong(result) == 0
    assert result["ids"] == order


@pytest.mark.parametrize("fault", ["flipped_byte", "dropped", "doubled",
                                   "swapped"])
def test_fault_is_caught(gen, fault):
    grids, _ = stream(gen)
    if fault == "flipped_byte":
        b = bytearray(grids[3])
        b[100] ^= 1
        grids[3] = bytes(b)
    elif fault == "dropped":
        del grids[3]
    elif fault == "doubled":
        grids.insert(4, grids[3])
    else:
        grids[0], grids[3] = grids[3], grids[0]
    assert wrong(check_consumed(gen, grids, WRITTEN)) >= 1
