"""The model-FLOP count the benchmark's ``mfu`` rests on."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.flops import flops_per_step, flops_per_token, matmul_params  # noqa: E402

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_granite_cut_step_is_51_95_tflop():
    m = json.loads((CONFIGS / "granite8b-pretrain.json").read_text())["model"]
    # 6 * 461.4M matmul params * 16384 tokens + 12 * 2 * 4096 * 4096 * 16384
    assert matmul_params(m) == 461373440
    assert flops_per_step(m, 4, 4096) == 51951924412416.0
    assert flops_per_token(m, 4096) == 51951924412416.0 / (4 * 4096)
