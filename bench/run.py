"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The run exits
non-zero with no result line unless JAX's first device is a TPU whose
``device_kind`` is in ``bench/peaks.py`` and there are as many devices as
the cell asks for. It keeps JAX's persistent compilation cache in
``<checkout>/.jax_cache``, makes its weights and data from ``--seed``, warms
the cell's step up, measures for ``--seconds``, then checks what the timed
path produced against the plain references. With ``--trace 1`` the window
is profiled and the line carries the cell's per-layer metrics; with
``--trace 0`` its end-to-end metrics. The numbers compared, each beside its
limit, are the last lines on standard error and the ``checks`` key of the
result line, the last line on standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
CACHE_DIR = CHECKOUT / ".jax_cache"
METRICS_DIR = Path(__file__).resolve().parent / "metrics"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax() -> None:
    """The compile cache at its fixed path, libtpu's logs off."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_device(chips: int) -> dict:
    """The devices JAX found; ``SystemExit`` unless they are at least
    ``chips`` TPUs of a kind in the peak table."""
    import jax
    from bench.peaks import PEAKS
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {dev.platform} "
                         f"({dev.device_kind}); nothing ran")
    if dev.device_kind not in PEAKS:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{dev.device_kind!r}; nothing ran")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}; nothing ran")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def cell_metrics(spec: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, run) -> float | None:
    """Call ``bench/metrics/<name>.py``'s ``read(run)``."""
    path = METRICS_DIR / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


END_TO_END = {
    "tokens_per_s": lambda r: r.window_tokens / r.window_s,
    "setup_s": lambda r: r.setup_s,
    "resume_s": lambda r: r.resume_s,
}


def main(argv=None) -> int:
    args = parse(argv)
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    configure_jax()
    from bench import harness
    cell = harness.load_cell(CHECKOUT, args.workload)
    device = check_device(cell.chips)
    limits = cell.config["limits"]
    trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if args.trace else None
    try:
        run = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               limits=limits, trace_dir=trace_dir)
        if args.trace:
            from bench.trace_reduce import reduce_trace
            path = next(trace_dir.rglob("*.xplane.pb"))
            run.trace = reduce_trace(str(path), "train_step")
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    kind = "per_layer" if args.trace else "end_to_end"
    for m in cell_metrics(spec, cell.name, kind):
        value = (read_metric(m["name"], run) if args.trace
                 else END_TO_END[m["name"]](run))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = run.memory_peak_bytes
    losses = [t.loss for r in run.reports for t in r.timings]
    correct = all(v <= lim for v, lim in run.checks.values())
    out = {"correct": correct, "attempted": len(losses),
           "failed": sum(not math.isfinite(x) for x in losses),
           "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    for k, (v, lim) in run.checks.items():
        print(f"check {k}: {v!r} (limit {lim!r}) "
              f"{'ok' if v <= lim else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
