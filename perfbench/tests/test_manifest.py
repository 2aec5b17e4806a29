"""``BENCHMARK.json`` against the contract it was written to, and every
name in it resolved to a file."""

import json
import os
import re

import pytest

from perfbench import manifest
from perfbench.tests.toy import (
    CELLS_OF_A_LATER_PR,
    TOY_CELLS,
    manifest_of_a_later_pr,
    manifest_with,
    toy_job,
    toy_manifest,
)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "d_model", "d_ff", "d_head")
# The two configurations accepted so far, by name: what is held of them
# alone and of no configuration that a later PR adds. Both are GPT-3 6.7B
# (Brown et al. 2020, table 2.1) at every published width, under the job
# that was the only one when they were accepted.
ACCEPTED_CONFIGS = ("gpt3-6.7b", "gpt3-6.7b-tp4")
GPT3_6_7B_WIDTHS = {"d_model": 4096, "n_heads": 32, "d_head": 128, "d_ff": 16384, "max_seq_len": 2048}


@pytest.fixture(
    scope="module",
    params=["accepted", "with_a_later_prs_cells", "with_a_later_prs_entries"],
)
def m(request):
    """Every check on the manifest runs three times: on ``BENCHMARK.json``
    as it is, on a copy that holds what a later configuration brings (a
    configuration naming a job of its own, with two cells), and on a
    copy with one configuration, one cell, that cell's name in an
    end-to-end list and one per-layer entry appended at the ends of the
    lists, so that no check here has to be edited when such entries
    come."""
    if request.param == "accepted":
        return manifest.load_manifest()
    if request.param == "with_a_later_prs_entries":
        return manifest_of_a_later_pr()
    return manifest_with(CELLS_OF_A_LATER_PR)


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(m):
    assert set(m) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(os.path.join(manifest.CHECKOUT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["paths"]) <= 16 and all(PATH.match(p) for p in m["paths"])
    assert len(m["command"]) <= 32 and all(one_line(w) for w in m["command"])
    full = len(m["workloads"]) * 14 + 2
    budget = full * (m["run_seconds"] + 60) + len(m["workloads"]) * 180 + 1200
    assert budget <= 43200
    # and with the full 24 cells that later PRs may bring
    assert (24 * 14 + 2) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(m):
    assert 1 <= len(m["configs"]) <= 24
    names = [c["name"] for c in m["configs"]]
    files = [c["file"] for c in m["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert c["name"] in used
        assert len(c["reduced"]) <= 16
        with open(os.path.join(manifest.CHECKOUT, c["file"])) as f:
            config = json.load(f)
        assert config["reduced"] == c["reduced"]
        # ``published``: the source's value of every reduced key, no other
        assert set(config.get("published", {})) == set(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(word in key for word in WIDTH_WORDS)
            assert config["published"][key] != config[key]
        if c["name"] in ACCEPTED_CONFIGS:
            assert {k: config[k] for k in GPT3_6_7B_WIDTHS} == GPT3_6_7B_WIDTHS
    assert set(ACCEPTED_CONFIGS) <= set(names)


def test_workloads(m):
    assert 1 <= len(m["workloads"]) <= 24
    names = [w["name"] for w in m["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        assert w["config"] in {c["name"] for c in m["configs"]}


def test_metrics(m):
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    every = m["end_to_end"] + m["per_layer"]
    names = [x["name"] for x in every]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in m["workloads"]}
    for x in every:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher") and x["source"] in SOURCES
        assert set(x.get("workloads", ())) <= cells
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    setup = [x for x in m["end_to_end"] if x["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    layers = {x["layer"] for x in m["per_layer"]}
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert one_line(x["layer"]) and x["layer"] in layers


def test_every_cell_reports_enough_and_every_arrow_lands(m):
    by_name = {x["name"]: x for x in m["end_to_end"]}
    for w in m["workloads"]:
        e2e = [x["name"] for x in manifest.metrics_of(m, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_of(m, "per_layer", w["name"])
    for x in m["per_layer"]:
        moved = by_name[x["moves"]]
        for cell in x.get("workloads", [w["name"] for w in m["workloads"]]):
            assert "workloads" not in moved or cell in moved["workloads"], (
                f"{x['name']} moves {x['moves']}, which {cell} does not report"
            )


def test_every_name_resolves_to_a_file(m):
    for w in m["workloads"]:
        cell = manifest.resolve_cell(m, w["name"])
        assert os.path.isfile(cell.loop_path)
        assert set(cell.reader_paths) == {x["name"] for x in cell.per_layer}
        for path in cell.reader_paths.values():
            assert callable(manifest.load_module(path).read)
        assert callable(manifest.load_module(cell.loop_path).run)
        assert cell.traffic["loop"]
        # the job: the file a directory of ``paths`` holds under the name
        # the configuration gives, whatever else that configuration says
        assert cell.job_path in [
            os.path.join(manifest.CHECKOUT, p, "jobs", cell.config["job"] + ".py")
            for p in m["paths"]
        ]
        assert callable(manifest.load_module(cell.job_path).make_job)


def test_files_under_paths_are_named_from_allowed_characters(m):
    for path in m["paths"]:
        for directory, dirs, files in os.walk(os.path.join(manifest.CHECKOUT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(directory, name), manifest.CHECKOUT)
                assert PATH.match(rel), rel


def test_a_cell_is_added_as_files_and_entries_alone():
    """The toy cells come in through a copy of the manifest and files of
    their own, with no edit to a file the benchmark has."""
    m = toy_manifest()
    for name, (config, traffic, chips, _) in TOY_CELLS.items():
        cell = manifest.resolve_cell(m, name)
        assert cell.chips == chips and cell.config_name == config
        assert cell.traffic_name == traffic
        assert "tests/data" in os.path.relpath(
            os.path.join(manifest.CHECKOUT, "perfbench/tests/data/traffic", traffic + ".json"),
            manifest.CHECKOUT,
        )
        assert cell.end_to_end and cell.per_layer
    # a per-layer metric too: a reader of its own, found by name
    m["per_layer"].append(
        {"name": "toy_reader", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "device", "moves": "resume_s",
         "workloads": ["toy.kill_resume"]}
    )
    cell = manifest.resolve_cell(m, "toy.kill_resume")
    reader = manifest.load_module(cell.reader_paths["toy_reader"])
    assert reader.read({"cycles": [1, 2, 3]}) == 3 and reader.read({}) is None


# ---- the job seam: a configuration names its job, found as a loop kind is

JOBS_OF_THE_TOYS = {"toy": "transformer_sgd", "toy-tp4": "transformer_sgd",
                    "toy-mixed": "toy_mixed", "toy-mixed-tp4": "toy_mixed"}


def _job_files(m):
    """Configuration name -> its job's file, relative to the checkout."""
    return {
        w["config"]: os.path.relpath(
            manifest.resolve_cell(m, w["name"]).job_path, manifest.CHECKOUT
        )
        for w in m["workloads"]
    }


def test_a_configuration_names_its_job(m, tmp_path):
    found = _job_files(m)
    for name in ACCEPTED_CONFIGS:
        assert found[name] == "perfbench/jobs/transformer_sgd.py"
    found = _job_files(toy_manifest())
    for name, job in JOBS_OF_THE_TOYS.items():
        where = "perfbench/tests/data/jobs" if job == "toy_mixed" else "perfbench/jobs"
        assert found[name] == f"{where}/{job}.py"
    # a configuration that names no job, and a job that no directory of
    # ``paths`` holds, are said aloud: the file, the places tried
    toys = toy_manifest()
    entry = next(c for c in toys["configs"] if c["name"] == "toy-mixed")
    entry["file"] = str(tmp_path / "lost.json")
    (tmp_path / "lost.json").write_text(json.dumps({"width": 256}))
    with pytest.raises(KeyError, match="lost.json names no job"):
        manifest.resolve_cell(toys, "toy-mixed.kill_resume")
    (tmp_path / "lost.json").write_text(json.dumps({"job": "nowhere"}))
    with pytest.raises(FileNotFoundError, match="tests/data/jobs/nowhere.py"):
        manifest.resolve_cell(toys, "toy-mixed.kill_resume")


def test_harness_and_loops_import_no_job_and_name_no_key_of_its_state():
    """What only a job may know: which module it is and how its state is
    keyed."""
    for rel in ("harness.py", "loops/save_in_loop.py", "loops/kill_resume.py", "run.py"):
        with open(os.path.join(manifest.BENCH_DIR, rel)) as f:
            text = f.read()
        assert '"params"' not in text and "'params'" not in text, rel
        assert not re.search(r"^\s*(from|import)\s+perfbench\.jobs?\b", text, re.M), rel
        assert not re.search(r"from\s+perfbench\s+import\s+.*\bjobs?\b", text), rel


def test_the_second_job_came_as_files_under_tests_alone():
    """Nothing of the benchmark outside ``tests/`` mentions the toy job or
    its configurations."""
    for directory, dirs, files in os.walk(manifest.BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "tests")]
        for name in files:
            path = os.path.join(directory, name)
            with open(path, errors="replace") as f:
                text = f.read()
            assert "toy_mixed" not in text and "toy-mixed" not in text, path


def _job_of(config_name):
    name = next(n for n, c in TOY_CELLS.items() if c[0] == config_name)
    return toy_job(name, seed=2**31 + 11)


@pytest.mark.parametrize("config_name", sorted(JOBS_OF_THE_TOYS))
def test_a_job_keeps_the_contract_the_harness_and_the_loops_use(config_name):
    """``perfbench/README.md``, "The job's contract": all that
    ``harness.py`` and the two loop kinds ask of a job."""
    import jax
    import numpy as np

    from perfbench import reference

    cell, job = _job_of(config_name)
    shapes = jax.tree.leaves(job.shapes)
    assert all(isinstance(s, jax.ShapeDtypeStruct) for s in shapes)
    assert all(np.dtype(s.dtype).itemsize in (1, 2, 4) for s in shapes)
    assert job.state_bytes == sum(
        int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize for s in shapes
    )
    assert list(job.devices) == jax.devices()[: cell.chips]

    def same_kind(tree):
        assert jax.tree.structure(tree) == jax.tree.structure(job.shapes)
        for leaf, s in zip(jax.tree.leaves(tree), shapes):
            assert (leaf.shape, leaf.dtype) == (s.shape, s.dtype)
            assert set(leaf.sharding.device_set) <= set(job.devices)

    state = job.init_state()
    same_kind(state)
    again = job.init_state()  # from the seed alone
    checksum = reference.make_checksum_fn()
    np.testing.assert_array_equal(checksum(state), checksum(again))
    for layout in (None, cell.traffic.get("check_layout")):
        zeros = job.template(layout)
        same_kind(zeros)
        assert not np.asarray(checksum(zeros)).any()
    # tokens: seed and step alone
    np.testing.assert_array_equal(job.tokens(3), job.tokens(3))
    assert (np.asarray(job.tokens(3)) != np.asarray(job.tokens(4))).any()
    # the app state: Statefuls under keys of the job's own, and back
    app = job.app_state(state, 7)
    assert all(callable(s.state_dict) and callable(s.load_state_dict) for s in app.values())
    assert job.step_of(app) == 7
    assert all(a is b for a, b in zip(jax.tree.leaves(job.state_of(app)), jax.tree.leaves(state)))
    # the warm-up saves one leaf a shape, not a state: any tree of arrays goes
    few = job.app_state({"leaf0": jax.tree.leaves(state)[0]}, 0)
    held = [x for s in few.values() for x in jax.tree.leaves(s.state_dict()) if hasattr(x, "shape")]
    assert len(held) == 1 and held[0] is jax.tree.leaves(state)[0]
    # the step: donating, fenced, loss fetched, the state's kind kept
    first = jax.tree.leaves(state)[0]
    stepped, loss = job.train_step(state, 0)
    assert isinstance(loss, float) and np.isfinite(loss)
    same_kind(stepped)
    _, loss_again = job.train_step(again, 0)
    assert loss_again == loss
    assert first.is_deleted() or jax.default_backend() == "cpu"


PARENT_SNAPSHOT = os.path.join(
    manifest.BENCH_DIR, "tests", "data", "snapshots", "parent-7b15f7e"
)


def test_transformer_sgd_restores_what_the_parent_of_pr_27_saved(tmp_path):
    """``data/snapshots/parent-7b15f7e``: a state of ``job.py`` as it was
    before it moved, saved by that tree's ``CheckpointManager.save(3,
    TrainJob.app_state(params, 3))`` (``write.py`` there, run from a
    ``git archive`` of 7b15f7e), with the sums that tree's reference gave
    the state. The job has to spell its app state so that this restores:
    ``train/params/<leaf>`` and ``progress/step``, through a plain
    ``PytreeStateful``."""
    import shutil

    import jax
    import numpy as np

    from perfbench import reference
    from torchsnapshot_tpu import CheckpointManager, PytreeStateful, StateDict

    with open(os.path.join(PARENT_SNAPSHOT, "config.json")) as f:
        config = json.load(f)
    with open(os.path.join(PARENT_SNAPSHOT, "sums.json")) as f:
        pinned = np.array(json.load(f)["sums"], dtype=np.uint32)
    # a copy: a restore may write beside what it reads, and the marker
    # of a step holds the directory's own path
    base = tmp_path / "ckpt"
    shutil.copytree(os.path.join(PARENT_SNAPSHOT, "step-3"), base / "step-3")
    (base / ".steps").mkdir()
    (base / ".steps" / "3").write_text(str(base / "step-3"))

    job = manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "jobs", config["job"] + ".py")
    ).make_job(config, jax.devices()[:1], seed=1)
    checksum = reference.make_checksum_fn()
    target = job.app_state(job.template(), -1)
    assert not np.asarray(checksum(job.state_of(target))).any()
    assert CheckpointManager(str(base)).restore(target) == 3
    assert job.step_of(target) == 3
    restored = job.state_of(target)
    assert len(jax.tree.leaves(restored)) == len(pinned) == 11
    np.testing.assert_array_equal(np.asarray(checksum(restored)), pinned)
    # and the spelling itself, as the parent had it in ``job.py``
    app = job.app_state(restored, 3)
    assert list(app) == ["train", "progress"]
    assert type(app["train"]) is PytreeStateful and type(app["progress"]) is StateDict
    assert app["train"].state_dict() == {"params": restored}
    assert app["progress"].state_dict() == {"step": 3}
    assert "embed" in restored  # the first leaf a lossy save names (PERF.md)
