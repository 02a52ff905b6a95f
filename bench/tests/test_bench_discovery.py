"""The harness finds a configuration, a workload and a metric by name from
their files, and ``run.py`` refuses to run without a TPU."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(__file__)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(HERE, ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import harness  # noqa: E402

FIXTURE = harness.Path(HERE) / "fixtures" / "root"


def test_every_cell_of_the_benchmark_resolves():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.cfg["hidden_size"] > 0 and cell.traffic["tenants"]
        assert set(cell.limits) >= {"loss_gap", "grad_gap", "change_gap"}
        for m in cell.per_layer:
            assert callable(harness.load_metric(m["name"]).read)


def test_a_new_cell_is_found_by_its_files_alone():
    cell = harness.find_cell("tiny-gelu.train", FIXTURE)
    assert cell.config_name == "tiny-gelu" and cell.traffic_name == "tiny-train"
    assert cell.cfg["block"]["mlp"] == "gelu"
    assert [t["method"] for t in cell.traffic["tenants"]] == [
        "lora", "adapter", "prefix", "lora"]
    assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap"}
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s", "peak_hbm_gib", "setup_s"]


def test_a_metric_reader_is_found_by_its_name():
    mod = harness.load_metric("planner.pad_share")

    class M:
        def __init__(self, tok, eff):
            self.tokens, self.effective_tokens = tok, eff

    class W:
        steps = [M(100, 75), M(100, 75)]

    assert mod.read({"window": W()}) == 25.0


def test_program_config_must_run_the_files_sizes():
    cell = harness.find_cell("tiny.train", FIXTURE)
    assert harness.arch_config(cell.cfg).d_model == 64
    wider = json.loads(json.dumps(cell.cfg))
    wider["program"]["overrides"]["d_ff"] = 96
    # the file's dense block, the program's hybrid family at the file's sizes
    other_family = json.loads(json.dumps(cell.cfg))
    other_family["program"]["arch"] = "zamba2-2.7b"
    for bad in (wider, other_family):
        try:
            harness.arch_config(bad)
        except RuntimeError:
            continue
        raise AssertionError(f"a program config that differs from the file "
                             f"ran: {bad['program']}")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.ROOT / "bench" / "run.py"), "--workload",
         "smollm-360m.train-solo", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "platform: cpu" in p.stderr

