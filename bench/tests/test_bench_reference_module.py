"""The model is a property of the configuration: its ``"reference"`` key
names the module of ``bench/reference/`` that the harness takes the
program's config, the weights, the reference step and the model FLOPs from.

A second model needs no edit of the harness: a module found on
``bench.reference.__path__`` and a configuration naming it run end to end.
Granite, reached through its key, reads what it read when the harness named
it."""
import copy
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import bench.reference  # noqa: E402
from bench import flops  # noqa: E402
from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.harness import Run  # noqa: E402
from bench.reference import granite  # noqa: E402
from bench.tests.smoke import smoke_cell, smoke_config  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG_FILES = {c["name"]: ROOT / c["file"] for c in SPEC["configs"]}
#: the per-layer metrics that read no particular model or layer of the
#: program, so that every cell lists itself in their ``workloads``
MODEL_AGNOSTIC = ("mfu", "step.device_ms", "device.idle_share",
                  "pipeline.data_wait_share", "read.fetch_ms",
                  "step.host_gap_ms")

OTHER = "other_arch"
OTHER_MODULE = '''"""A second model: Granite's reference under another name, with a
smoke size of its own."""
from bench.reference import granite as _granite

program_config = _granite.program_config
seed_words = _granite.seed_words
make_init = _granite.make_init
leaf_paths = _granite.leaf_paths
leaf_norms = _granite.leaf_norms
change_norms = _granite.change_norms
reference_steps = _granite.reference_steps
FLOPS_CALLS = []


def flops_per_token(model, seq_len):
    FLOPS_CALLS.append(seq_len)
    return _granite.flops_per_token(model, seq_len)


SMOKE = {"model": dict(_granite.SMOKE["model"], num_hidden_layers=1),
         "limits": dict(_granite.SMOKE["limits"])}
'''


def config(name: str) -> dict:
    return json.loads(CONFIG_FILES[name].read_text())


@pytest.fixture
def other_arch(tmp_path, monkeypatch):
    """``bench.reference.other_arch``, a module in ``tmp_path``."""
    (tmp_path / f"{OTHER}.py").write_text(OTHER_MODULE)
    monkeypatch.setattr(bench.reference, "__path__",
                        [*bench.reference.__path__, str(tmp_path)])
    yield OTHER
    sys.modules.pop(f"bench.reference.{OTHER}", None)
    if hasattr(bench.reference, OTHER):
        delattr(bench.reference, OTHER)


def test_a_new_reference_module_runs_with_no_edit(other_arch, tmp_path,
                                                  monkeypatch, capsys):
    # a checkout whose BENCHMARK.json adds the configuration and its cell,
    # listed in the model-agnostic per-layer metrics' workloads
    checkout = tmp_path / "checkout"
    cfg = config("granite8b-pretrain")
    cfg.update(name="other-smoke", reference=other_arch)
    cfg = smoke_config(cfg)
    file = checkout / "bench" / "configs" / "other-smoke.json"
    file.parent.mkdir(parents=True)
    file.write_text(json.dumps(cfg))
    cell = "other-smoke.steady"
    spec = copy.deepcopy(SPEC)
    spec["configs"].append({"name": "other-smoke", "source": "test",
                            "file": "bench/configs/other-smoke.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "other-smoke",
                              "traffic": "steady", "chips": 1, "why": "t"})
    for m in spec["per_layer"]:
        if m["name"] in MODEL_AGNOSTIC:
            m["workloads"].append(cell)
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))

    runs = []
    real_run_cell = harness.run_cell

    def run_cell(*a, **kw):
        runs.append(real_run_cell(*a, **kw))
        return runs[-1]
    monkeypatch.setattr(bench_run, "CHECKOUT", checkout)
    monkeypatch.setattr(bench_run, "configure_jax", lambda: None)
    monkeypatch.setattr(bench_run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    monkeypatch.setattr(harness, "run_cell", run_cell)
    assert bench_run.main(["--workload", cell, "--seed", str(2**33 + 5),
                           "--seconds", "0.3", "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {
        m["name"] for m in bench_run.cell_metrics(spec, cell, "end_to_end")}
    assert line["attempted"] > 0 and line["failed"] == 0

    run = runs[0]
    assert run.config["model"]["num_hidden_layers"] == 1   # its own SMOKE
    other = harness.reference(run.config)
    assert other.__name__ == f"bench.reference.{OTHER}"
    run.device_kind = "TPU v5 lite"
    run.trace = {"window_s": run.window_s, "busy_s": run.window_s,
                 "devices": 1}
    seq = run.config["train"]["seq_len"]
    want = 100 * granite.flops_per_token(run.config["model"], seq) \
        * run.window_tokens / run.window_s / 197e12
    assert bench_run.read_metric("mfu", run) == pytest.approx(want, rel=1e-12)
    assert other.FLOPS_CALLS == [seq]


@pytest.mark.parametrize("reference", [None, "no_such_model"])
def test_a_configuration_must_name_a_reference_that_exists(reference):
    cfg = config("granite8b-pretrain")
    if reference is None:
        del cfg["reference"]
    else:
        cfg["reference"] = reference
    for lookup in (harness.reference, harness.model_config):
        with pytest.raises(SystemExit) as e:
            lookup(cfg)
        assert "granite8b-pretrain" in str(e.value.code)
        assert f"bench/reference/{reference or '<name>'}.py" \
            in str(e.value.code)


@pytest.mark.parametrize("name", sorted(CONFIG_FILES))
def test_granite_configs_name_granite(name):
    assert harness.reference(config(name)) is granite


def test_granite_weights_are_the_seeds_draw():
    cfg = smoke_cell("granite8b-pretrain", "steady").config
    seed = 2**34 + 3
    params, opt = harness.make_state(cfg, seed)
    # one jitted call, as the reference draws them (eager draws differ
    # from it in the last bit)
    want = jax.jit(granite.make_init(cfg["model"]))(granite.seed_words(seed))
    got_leaves = jax.tree_util.tree_leaves_with_path(params)
    want_leaves = jax.tree_util.tree_leaves_with_path(want)
    assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
    for (path, a), (_, b) in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name", sorted(CONFIG_FILES))
def test_granite_program_config_is_unchanged(name):
    from repro.models import ModelConfig
    assert harness.model_config(config(name)) == ModelConfig(
        name=name, family="dense", num_layers=2, d_model=4096, num_heads=32,
        num_kv_heads=8, head_dim=128, d_ff=14336, vocab_size=6144,
        rope_theta=10000.0, norm_eps=1e-05, tie_embeddings=False,
        param_dtype="float32", compute_dtype="bfloat16")


@pytest.mark.parametrize("name", sorted(CONFIG_FILES))
def test_granite_mfu_is_the_dense_stack_formula(name):
    cfg = config(name)
    run = Run(window_s=10.0, window_tokens=12 * 16384, config=cfg,
              device_kind="TPU v5 lite",
              trace={"window_s": 10.0, "busy_s": 9.0, "devices": 1})
    per_token = flops.flops_per_token(cfg["model"], cfg["train"]["seq_len"])
    assert per_token == 51951924412416.0 / 16384
    assert bench_run.read_metric("mfu", run) == \
        100.0 * per_token * (12 * 16384 / 10.0) / 197e12
