"""A cell at smoke size for the CPU tests: the model cut by its reference
module's ``SMOKE`` (tiny widths, and limits read at that size), a small
batch, a store model a hundred times faster and a short checkpoint
cadence."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
TRAFFIC = ROOT / "bench" / "traffic"


def smoke_config(cfg: dict) -> dict:
    """A copy of the configuration ``cfg`` at smoke size."""
    cfg = copy.deepcopy(cfg)
    cfg["train"].update(global_batch=4, seq_len=64, microbatches=2)
    cfg["data_plane"]["producer_lead"] = 4
    for k in ("put_base_s", "get_base_s", "list_base_s", "delete_base_s",
              "head_base_s"):
        cfg["store"][k] /= 100.0
    if cfg.get("checkpoint_every"):
        cfg["checkpoint_every"] = 3
    for section, values in harness.reference(cfg).SMOKE.items():
        cfg[section].update(values)
    return cfg


def smoke_cell(config: str, traffic: str) -> harness.Cell:
    """The configuration ``config`` of ``BENCHMARK.json`` at smoke size,
    under the traffic ``traffic``."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    file = {c["name"]: c["file"] for c in spec["configs"]}[config]
    cfg = smoke_config(json.loads((ROOT / file).read_text()))
    tr = json.loads((TRAFFIC / f"{traffic}.json").read_text())
    return harness.Cell(f"{config}.{traffic}", cfg, tr, 1)
