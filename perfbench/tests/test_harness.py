"""Both loop kinds end to end at a toy size; a run owns its root; every
planted fault turns ``correct`` false and says where."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import faults, manifest
from perfbench.tests.toy import TOY_CELLS, run_toy, toy_job, toy_manifest

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


@pytest.mark.parametrize(
    "name",
    ["toy.save_in_loop", "toy.kill_resume", "toy-mixed.save_in_loop", "toy-tp4.reshard_resume"],
)
def test_traced_run_reports_per_layer_metrics(name, dirs):
    line = run_toy(name, trace=True, **dirs)
    assert line["correct"] is True, line
    cell = manifest.resolve_cell(toy_manifest(), name)
    named = {m["name"] for m in cell.per_layer}
    assert set(line["metrics"]) <= named
    # No device plane on the CPU backend: the device trace's readers find
    # nothing and are left out; they do not report a 0. Every other
    # metric the cell lists is there.
    traced = {m["name"] for m in cell.per_layer if m["source"] == "device_trace"}
    assert not any(n.startswith("device_idle_pct") for n in line["metrics"])
    assert not traced & set(line["metrics"])
    assert set(line["metrics"]) >= named - traced
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
    # The same five under the second job, whose app state is spelled
    # otherwise (``lossy_save`` in ``kill_resume``: the test below).
    ("toy-mixed.save_in_loop", "lossy_save"),
    ("toy-mixed.save_in_loop", "restore_lands_nothing"),
    ("toy-mixed.save_in_loop", "restore_lands_half"),
    ("toy-mixed.save_in_loop", "corrupt_newest_object"),
    ("toy-mixed.kill_resume", "restore_lands_nothing"),
    ("toy-mixed.kill_resume", "restore_lands_half"),
    ("toy-mixed-tp4.save_in_loop", "restore_swaps_shards"),
    # A restore onto another layout: the control, a state left as the
    # template gave it, half of it landed, the shards exchanged wrongly,
    # and the second copy of every leaf (dp = 1) left as the template
    # gave it.
    ("toy-tp4.reshard_resume", "lossy_save"),
    ("toy-tp4.reshard_resume", "restore_lands_nothing"),
    ("toy-tp4.reshard_resume", "restore_lands_half"),
    ("toy-tp4.reshard_resume", "restore_swaps_shards"),
    ("toy-tp4.reshard_resume", "restore_lands_one_replica"),
]
# How each toy job spells the leaves of its app state on disk.
LEAF_PREFIX = {"toy": "train/params/", "toy-mixed": "everything/"}


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
        assert first["leaves_that_raise"][0]["leaf"].startswith(
            LEAF_PREFIX[TOY_CELLS[name][0]]
        )
    written = os.listdir(dirs["out_dir"])
    assert any(f.startswith(f"diagnosis-{name}-5") for f in written)
    assert os.listdir(dirs["roots_parent"]) == []


def test_the_control_rounds_every_matrix_of_the_second_jobs_state(dirs, capsys):
    """What ``lossy_save`` cannot touch in ``toy_mixed``'s state: the
    vectors (``b_in``, ``scale`` and both their moments) and the int32
    count, for it rounds matrices alone. Every matrix comes back
    differing in every cycle: the float32 ones (``w_in``, all moments)
    rounded to bfloat16, the bfloat16 ones (``embed``, ``w_out``) to
    float8."""
    import jax

    with faults.FAULTS["lossy_save"]():
        line = run_toy("toy-mixed.kill_resume", **dirs)
    assert line["correct"] is False, line
    said = [
        json.loads(ln.split("MISMATCH ", 1)[1])
        for ln in capsys.readouterr().out.splitlines()
        if "MISMATCH" in ln
    ]
    _, job = toy_job("toy-mixed.kill_resume")
    flat, _ = jax.tree_util.tree_flatten_with_path(job.shapes)
    matrices = {jax.tree_util.keystr(p) for p, s in flat if len(s.shape) >= 2}
    assert {d["leaf"] for d in said} == matrices
    assert len(matrices) == 15 and len(flat) == 28
    float32 = {jax.tree_util.keystr(p) for p, s in flat if s.dtype == "float32"}
    assert len(matrices & float32) == 12
    assert line["compared"]["leaves_differing"]["value"] == 15 * line["attempted"]


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


def test_the_read_bytes_reader_on_recorded_cycles_and_on_none():
    """``restore_read_bytes_ratio``: the mean bytes a cycle read over the
    bytes of the stored objects; nothing where a cycle's count is
    missing (a program whose report has no such field) or no loop
    recorded one."""
    path = os.path.join(manifest.BENCH_DIR, "layers", "restore_read_bytes_ratio.py")
    read = manifest.load_module(path).read
    cycles = [{"restore_s": 1.0, "read_bytes": 200}, {"restore_s": 1.0, "read_bytes": 400}]
    assert read({"cycles": cycles, "stored_object_bytes": 200}) == 1.5
    assert read({"cycles": cycles + [{"read_bytes": None}], "stored_object_bytes": 200}) is None
    assert read({"cycles": [{"restore_s": 1.0}], "stored_object_bytes": 200}) is None
    assert read({"cycles": cycles}) is None and read({}) is None


def test_a_restore_onto_another_layout_reads_what_was_stored(dirs):
    """The toy's state saved on dp1 x tp4 and restored on dp2 x tp2 in
    every cycle: each cycle's bytes read are counted, the objects' bytes
    are those of the state, and the first step runs on the new layout."""
    line = run_toy("toy-tp4.reshard_resume", trace=True, **dirs)
    assert line["correct"] is True, line
    info = line["info"]
    assert info["saved_layout"] == {"dp": 1, "tp": 4}
    assert info["restore_layout"] == {"dp": 2, "tp": 2}
    _, job = toy_job("toy-tp4.reshard_resume")
    assert info["stored_object_bytes"] == job.state_bytes
    assert len(info["read_bytes"]) == info["cycles"] > 0
    assert all(n >= job.state_bytes for n in info["read_bytes"])
    assert line["metrics"]["restore_read_bytes_ratio"]["value"] >= 1.0
