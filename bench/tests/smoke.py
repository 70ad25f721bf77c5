"""A cell at smoke size for the CPU tests: Granite's layout with tiny
widths, a store model a hundred times faster, a short checkpoint cadence,
and limits read at that size."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from bench import harness

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def smoke_cell(config: str = "granite8b-ckpt16",
               traffic: str = "save-resume") -> harness.Cell:
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=2, head_dim=16,
                        intermediate_size=160, vocab_size=257)
    cfg["train"].update(global_batch=4, seq_len=64, microbatches=2)
    cfg["data_plane"]["producer_lead"] = 4
    for k in ("put_base_s", "get_base_s", "list_base_s", "delete_base_s",
              "head_base_s"):
        cfg["store"][k] /= 100.0
    if cfg.get("checkpoint_every"):
        cfg["checkpoint_every"] = 3
    # the smoke size's own limits, above what sound runs read on the CPU
    # (six seeds: loss 4.3e-4, gradient 4.8e-3, change 1.8e-3) and below
    # what the fp8 control read on three (1.3e-3, 2.7e-2, 3.8e-3)
    cfg["limits"].update(loss_gap=1e-3, grad_gap=1.2e-2, change_gap=3e-3)
    tr = json.loads((TRAFFIC / f"{traffic}.json").read_text())
    return harness.Cell(f"{config}.{traffic}", cfg, tr, 1)
