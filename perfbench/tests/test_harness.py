"""Both loop kinds end to end at a toy size; a run owns its root; every
planted fault turns ``correct`` false and says where."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import faults, manifest
from perfbench.tests.toy import TOY_CELLS, run_toy, toy_manifest

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture
def dirs(tmp_path):
    return {"out_dir": tmp_path / "out", "roots_parent": tmp_path / "roots"}


@pytest.mark.parametrize("name", sorted(TOY_CELLS))
def test_cell_runs_and_prints_the_contracts_keys(name, dirs, capsys):
    line = run_toy(name, **dirs)
    assert line["correct"] is True, line
    assert list(line)[: len(CONTRACT_KEYS)] == CONTRACT_KEYS
    assert list(line)[-1] == "compared"
    assert set(line["device"]) == DEVICE_KEYS
    assert line["attempted"] > 0 and line["failed"] == 0
    cell = manifest.resolve_cell(toy_manifest(), name)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["info"]["compiles_in_window"] == 0
    assert all(c["limit"] == 0 for c in line["compared"].values())
    assert json.loads(json.dumps(line)) == line
    assert os.listdir(dirs["roots_parent"]) == []  # the run removed its root


@pytest.mark.parametrize("name", ["toy.save_in_loop", "toy.kill_resume"])
def test_traced_run_reports_per_layer_metrics(name, dirs):
    line = run_toy(name, trace=True, **dirs)
    assert line["correct"] is True, line
    cell = manifest.resolve_cell(toy_manifest(), name)
    named = {m["name"] for m in cell.per_layer}
    assert set(line["metrics"]) <= named
    # No device plane on the CPU backend: the device reader finds nothing
    # and is left out; it does not report a 0.
    assert not any(n.startswith("device_idle_pct") for n in line["metrics"])
    assert len(line["metrics"]) >= len(named) - 1
    assert "setup_s" not in line["metrics"]


def test_second_run_is_blind_to_what_the_first_left_behind(dirs):
    """PR 22's refusal: a verdict that depended on an earlier run."""
    first = run_toy("toy.save_in_loop", seed=1, **dirs)
    assert first["correct"] is True
    # A stale root with a committed-looking step far in the future, as a
    # killed run would leave it.
    stale = dirs["roots_parent"] / "run-stale" / "ckpt"
    (stale / ".steps").mkdir(parents=True)
    (stale / ".steps" / "999999").write_text(str(stale / "step-999999"))
    (stale / "step-999999").mkdir()
    second = run_toy("toy.save_in_loop", seed=2147483653, **dirs)
    assert second["correct"] is True, second
    assert os.listdir(dirs["roots_parent"]) == []


FAULTS_BY_CELL = [
    ("toy.save_in_loop", "lossy_save"),
    ("toy.save_in_loop", "restore_lands_nothing"),
    ("toy.save_in_loop", "restore_lands_half"),
    ("toy.save_in_loop", "corrupt_newest_object"),
    ("toy.kill_resume", "lossy_save"),
    ("toy.kill_resume", "restore_lands_nothing"),
    ("toy.kill_resume", "restore_lands_half"),
    ("toy-tp4.save_in_loop", "lossy_save"),
    ("toy-tp4.save_in_loop", "restore_swaps_shards"),
]


@pytest.mark.parametrize("name,fault", FAULTS_BY_CELL)
def test_planted_fault_turns_correct_false_and_names_the_leaf(
    name, fault, dirs, capsys
):
    with faults.FAULTS[fault]():
        line = run_toy(name, **dirs)
    assert line["correct"] is False, line
    said = [ln for ln in capsys.readouterr().out.splitlines() if "MISMATCH" in ln]
    assert said, "a run that finds a mismatch says what"
    first = json.loads(said[0].split("MISMATCH ", 1)[1])
    assert first["cell"] == name and first["seed"] == 5
    assert {"peak_bytes_in_use", "free_bytes_under_root", "capture_fallbacks"} <= set(first)
    if line["compared"].get("leaves_differing", {}).get("value"):
        assert first["leaf"].startswith("['") and "step" in first
    if fault == "corrupt_newest_object":
        assert line["compared"]["steps_unrestorable"]["value"] > 0
        assert first["leaves_that_raise"][0]["leaf"].startswith("train/params/")
    written = os.listdir(dirs["out_dir"])
    assert any(f.startswith(f"diagnosis-{name}-5") for f in written)
    assert os.listdir(dirs["roots_parent"]) == []


def test_cli_refuses_a_cpu_backend(tmp_path):
    checkout = manifest.CHECKOUT
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "gpt3-6.7b.kill_resume"]
        + ["--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=checkout,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
        capture_output=True,
        text=True,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "not 'tpu'" in done.stderr


def test_cli_fails_where_only_the_benchmark_is(tmp_path):
    """A directory that holds BENCHMARK.json and ``paths`` alone."""
    import shutil

    checkout = manifest.CHECKOUT
    shutil.copy(os.path.join(checkout, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(checkout, "perfbench"),
        tmp_path / "perfbench",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "gpt3-6.7b.kill_resume"]
        + ["--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True,
        text=True,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
