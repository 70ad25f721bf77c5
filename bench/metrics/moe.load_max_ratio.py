"""The fullest held expert's rows over a held expert's mean rows, per step:
``moe_rows_max`` over ``moe_rows_local`` / (held experts x expert layers x
microbatches), both args of the step's ``pipeline.sync`` span; the median
over the window's steps. 1 is even load. With no capacity to cut tokens the
fullest expert's rows pace its grouped matmul."""
import statistics


def read(run):
    m = (run.config or {}).get("model", {})
    micro = (run.config or {}).get("train", {}).get("microbatches")
    if "n_routed_experts" not in m or not micro:
        return None
    groups = m["n_routed_experts"] * micro * (
        m["num_hidden_layers"] - m.get("first_k_dense_replace", 0))
    ratios = [s.args["moe_rows_max"] * groups / s.args["moe_rows_local"]
              for s in run.spans if s.name == "pipeline.sync"
              and (getattr(s, "args", None) or {}).get("moe_rows_local")]
    return statistics.median(ratios) if ratios else None
