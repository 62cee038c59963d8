"""The harness finds every piece of a cell by the names in BENCHMARK.json,
and refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_cell_parts_found_by_name(cell):
    parts = harness.cell_files(spec(), cell)
    config = parts["config"]
    assert config["name"] == parts["cell"]["config"]
    assert "round_steps" in parts["mix"]
    assert {m["name"] for m in parts["end_to_end"]} >= {
        "session_steps_per_s", "setup_s"}
    for m in parts["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    assert set(parts["limits"]) >= {"learner_gap", "env_gap"}


def test_a_new_cell_is_files_and_an_entry(tmp_path, monkeypatch):
    """A cell added as data: a BENCHMARK.json entry and a limits file of
    its own (here the steady mix on the 64-session deployment)."""
    s = spec()
    s["workloads"].append({"name": "paper2d_64.steady", "config":
                           "paper2d_64", "traffic": "steady", "chips": 1,
                           "why": "test"})
    with pytest.raises(harness.BenchError, match="limits"):
        harness.cell_files(s, "paper2d_64.steady")
    limits = {"learner_gap": 0.1}
    (tmp_path / "paper2d_64.steady.json").write_text(json.dumps(limits))
    monkeypatch.setattr(harness, "LIMITS_DIR", str(tmp_path))
    parts = harness.cell_files(s, "paper2d_64.steady")
    assert parts["mix"]["turnover_share"] == 0.0
    assert parts["config"]["sessions"] == 64
    assert parts["limits"] == limits


def test_a_number_without_a_limit_stops_the_run():
    with pytest.raises(harness.BenchError, match="final_gap"):
        harness.with_limits({"env_gap": 0.0, "final_gap": 0.0},
                            {"env_gap": 1e-4})


def test_unknown_names_are_errors():
    with pytest.raises(harness.BenchError):
        harness.cell_files(spec(), "no_such.cell")
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no_such_metric")
    with pytest.raises(harness.BenchError):
        harness.device_peaks("no such device")


def test_configs_state_their_source_and_cuts():
    for c in spec()["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        # every assumed size is a key of the configuration, with its reason
        assert set(config["assumed"]) <= set(config)
        assert all(config["assumed"].values())


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "paper2d_64.churn", "--seed", "5000000000", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
