"""Model FLOPs utilisation of the whole step over the traced window, in %.

Model FLOPs per token (the ``flops_per_token`` of the configuration's
reference module: what the forward and backward passes require, no
recompute) times the window's trained tokens per second, over the bf16
peak of the device kind times the chips used. Checkpoint stalls and data
waits in the window count against it, as they do against ``tokens_per_s``.
"""
from bench import harness
from bench.peaks import peaks_for


def read(run):
    if not run.window_tokens or run.trace is None:
        return None
    cfg = run.config
    per_token = harness.reference(cfg).flops_per_token(
        cfg["model"], cfg["train"]["seq_len"])
    rate = run.window_tokens / run.window_s
    peak = peaks_for(run.device_kind)["bf16_flops"] * run.trace["devices"]
    return 100.0 * per_token * rate / peak
