"""Job ``mixed_adamw`` (``jobs/mixed_adamw.py``) over
``models/lfm2_moe.py``, its configuration and its cell under the loop
kind ``save_in_loop``: the cell of ``BENCHMARK.json`` resolved to files
that exist, its whole list of metrics, the configuration's published
keys, the state's exact counts (the expert biases among the params,
saved under ``model``), the job held to the job's contract at a toy
size, the save and resume loops end to end under this job, the reader
of the capture's headroom (``layers/capture_device_headroom_share.py``),
and SDAR's tree, which the same job file spells, as it was.

The toy configuration (``data/configs/toy-lfm2.json``) comes in as the
toy cells of ``toy.py`` do: files and entries in a copy of the
manifest.
"""

import json
import os
import time

import jax
import numpy as np
import pytest

from perfbench import harness, manifest, reference
from perfbench.tests.toy import manifest_of_a_later_pr, manifest_with

CELL = "lfm2-8b-a1b-ep4.save_in_loop"
NEMOTRON = "nemotron3-nano-30b-a3b-ep16.save_in_loop"
TOY_CELLS = {
    "toy-lfm2.save_in_loop": ("toy-lfm2", "toy_save_in_loop", 1, CELL),
    "toy-lfm2.kill_resume": ("toy-lfm2", "toy_kill_resume", 1, "gpt3-6.7b.kill_resume"),
}
# The catalog's ``config`` of LFM2-8B-A1B (config.json of
# LiquidAI/LFM2-8B-A1B), written out: every key is in the file at this
# value but the two in ``reduced`` that it names.
LAYER_TYPES = (
    ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
    + ["full_attention", "conv", "conv", "full_attention", "conv", "conv"]
)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536,
}
HBM = 16_909_336_064  # a v5e's bytes_limit
STAGING = [
    "stage_slice_thread_s", "stage_d2h_thread_s", "stage_copy_thread_s",
    "stage_checksum_thread_s", "stage_threads_busy",
]


def toy_manifest():
    return manifest_with(TOY_CELLS)


def toy_job(name, seed=2**31 + 13):
    cell = manifest.resolve_cell(toy_manifest(), name)
    job = manifest.load_module(cell.job_path).make_job(
        cell.config, jax.devices()[: cell.chips], seed
    )
    return cell, job


def run_toy(name, dirs, seed=5, seconds=1.0, trace=False):
    cell = manifest.resolve_cell(toy_manifest(), name)
    return harness.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace,
        devices=jax.devices()[: cell.chips], started_at=time.monotonic(),
        out_dir=str(dirs["out_dir"]), roots_parent=str(dirs["roots_parent"]),
    )


@pytest.fixture
def dirs(tmp_path):
    return {"out_dir": tmp_path / "out", "roots_parent": tmp_path / "roots"}


def nbytes(s):
    return int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize


# ------------------------------------------------- the cell, as accepted


def check_the_cell_resolves_to_files_that_exist(m):
    cell = manifest.resolve_cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "save_in_loop_lfm2"
    rel = lambda path: os.path.relpath(path, manifest.CHECKOUT)
    assert rel(cell.job_path) == "perfbench/jobs/mixed_adamw.py"
    assert rel(cell.loop_path) == "perfbench/loops/save_in_loop.py"
    assert cell.config["job"] == "mixed_adamw" and cell.config["model"] == "lfm2_moe"
    assert [x["name"] for x in cell.end_to_end] == ["loop_steps_per_s", "setup_s"]
    # the Nemotron save cell's sixteen, host staging's two among them,
    # and the capture's headroom on the device. The staging threads'
    # five would read the host staging inside async_save too, but their
    # accepted entries name the four-chip GPT-3 cell alone.
    assert [x["name"] for x in cell.per_layer] == [
        "save_durable_s", "step_p95_ms", "step_free_ms", "step_in_drain_ms",
        "loop_ms_lost_per_save", "save_call_blocked_ms", "capture_fallbacks",
        "drain_d2h_share", "drain_storage_share", "write_busy_share",
        "stored_bytes_ratio", "device_idle_pct.save", "capture_host_stage_ms",
        "capture_d2h_share", "save_idle_staging_ms",
        "save_idle_outside_library_ms", "capture_device_headroom_share",
    ]
    assert [x["name"] for x in manifest.metrics_of(m, "per_layer", NEMOTRON)] == [
        x["name"] for x in cell.per_layer if x["name"] != "capture_device_headroom_share"
    ]
    for x in m["per_layer"]:
        if x["name"] in STAGING:
            assert x["workloads"] == ["gpt3-6.7b-tp4.save_in_loop"]
    for path in cell.reader_paths.values():
        assert os.path.isfile(path)
    traffic = cell.traffic
    assert traffic["loop"] == "save_in_loop" and traffic["warm_steps"] == 3
    assert traffic["save_every_steps"] >= 100000 and traffic["keep"] == 2  # one save
    assert traffic["check_layout"] is None and traffic["profile_at_save"] == 0
    assert traffic["profile_seconds"] == 8.0
    assert 10 <= traffic["first_save_after_steps"] <= 25  # 5 s of free steps
    assert cell.config["mesh"] is None and cell.config["save_options"] == {}
    (config,) = [c for c in m["configs"] if c["name"] == "lfm2-8b-a1b-ep4"]
    assert config["file"] == "perfbench/configs/lfm2-8b-a1b-ep4.json"
    assert config["source"] == cell.config["source"]
    assert [w["name"] for w in m["workloads"]].count(CELL) == 1
    (headroom,) = [x for x in m["per_layer"] if x["name"] == "capture_device_headroom_share"]
    assert headroom["unit"] == "%" and headroom["layer"] == "take plan + capture"
    # a cell that clones, a cell that stages to the host, and this one
    assert headroom["workloads"] == [
        "gpt3-6.7b-tp4.save_in_loop", "nemotron3-nano-30b-a3b-ep16.save_every_50", CELL,
    ]


def test_benchmark_resolves_the_new_cell_to_files_that_exist():
    check_the_cell_resolves_to_files_that_exist(manifest.load_manifest())


def check_the_configuration_keeps_every_published_key(m):
    config = manifest.resolve_cell(m, CELL).config
    reduced = {"num_experts": 8, "vocab_size": 16384}
    assert {k: config[k] for k in PUBLISHED} == {**PUBLISHED, **reduced}
    assert config["reduced"] == ["layers_held", "num_experts", "vocab_size"]
    assert config["published"] == {"layers_held": 24, "num_experts": 32, "vocab_size": 65536}
    assert config["expert_ids"] == list(range(8))
    assert config["vocab_size"] * 4 == 65536 and config["num_experts"] * 4 == 32
    # the floor: the two leading dense layers and one whole period
    # (attention, conv, conv, conv) of the pattern that follows them
    held = config["layer_types"][: config["layers_held"]]
    assert held == ["conv", "conv", "full_attention", "conv", "conv", "conv"]
    assert config["layers_held"] - config["num_dense_layers"] >= 4
    assert "tie_word_embeddings" not in config  # the row has no such key
    assert config["expert_capacity"] == 0 and config["remat"] is True
    assert (config["seq_len"], config["batch_size"]) == (8192, 1)
    assert config["optimizer"] == {
        "name": "adamw", "lr": 0.0001, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
        "weight_decay": 0.1,
    }
    for key in ("tied_head", "expert_bias_update", "head_dim", "initial_values",
                "batch", "state"):
        assert config["assumed"][key], key
    with open(os.path.join(manifest.BENCH_DIR, "configs", "sdar-30b-a3b-ep8.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]  # word for word


def test_the_configuration_keeps_every_published_key():
    check_the_configuration_keeps_every_published_key(manifest.load_manifest())


def check_the_state_is_the_one_the_cell_is_for(m):
    """Sizes from shapes alone (nothing is allocated): 568.6M parameters
    at 14 B saved, with the count and four layers' expert biases, 47 %
    of HBM, 209 leaves; its clones would fit beside it (2 x 7.96e9 B
    under the limit), but not beside a step too."""
    cell = manifest.resolve_cell(m, CELL)
    job = manifest.load_module(cell.job_path).make_job(cell.config, jax.devices()[:1], 1)
    leaves = jax.tree.leaves(job.shapes)
    sizes = [nbytes(s) for s in leaves]
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    master = job.shapes["master"]
    n_params = count(master)
    assert n_params == 568_647_808
    assert [count(blk) for blk in master["layers"]] == (
        [60_827_648] * 2 + [98_635_904] + [104_933_376] * 3
    )
    assert count(master["embed"]) == 16384 * 2048 == 33_554_432  # also the head
    assert job.state_bytes == sum(sizes) == 14 * n_params + 4 + 4 * 32 * 4
    assert job.state_bytes == 7_961_069_828
    assert 0.47 < job.state_bytes / HBM < 0.472
    assert 2 * job.state_bytes < HBM < 2 * job.state_bytes + 16 * n_params // 8
    assert len(leaves) == 4 * 51 + 1 + 4 == 209
    assert min(sizes) == 4 and max(sizes) == 8 * 2048 * 2 * 1792 * 4 == 234_881_024
    assert [(s.shape, str(s.dtype)) for s in job.shapes["params"]["expert_bias"]] == (
        [((32,), "float32")] * 4
    )
    # the ``model`` Stateful holds the biases among the params
    app = job.app_state(job.shapes, -1)
    assert sorted(app["model"].tree) == ["master", "params"]
    assert "expert_bias" not in app["model"].tree["master"]
    model = jax.tree.leaves(app["model"].tree)
    assert len(model) == 2 * 51 + 4 == 106
    assert sum(nbytes(s) for s in model) == 6 * n_params + 512 == 3_411_887_360
    assert len(jax.tree.leaves(app["optimizer"].tree)) == 2 * 51 + 1
    params = job.shapes["params"]["layers"]
    assert params[0]["in_proj"].shape == (2048, 3 * 2048)
    assert params[0]["conv_w"].shape == (3, 2048) and params[0]["out_proj"].shape == (2048, 2048)
    assert params[0]["gate_up"].shape == (2048, 2 * 7168) and params[0]["down"].shape == (7168, 2048)
    assert params[2]["wq"].shape == (2048, 32 * 64) and params[2]["wk"].shape == (2048, 8 * 64)
    assert params[2]["q_norm"].shape == params[2]["k_norm"].shape == (64,)
    assert params[3]["gate_up"].shape == (8, 2048, 2 * 1792)
    assert params[3]["down"].shape == (8, 1792, 2048) and params[3]["router"].shape == (2048, 32)
    assert "head" not in job.shapes["params"]
    assert (job.batch, job.seq_len) == (1, 8192)


def test_the_state_is_the_one_the_cell_is_for():
    check_the_state_is_the_one_the_cell_is_for(manifest.load_manifest())


def check_sdar_s_tree_is_what_it_was(m):
    """The job file is shared: SDAR's saved tree, Stateful by Stateful,
    is what it was before a model held biases among its params."""
    cell = manifest.resolve_cell(m, "sdar-30b-a3b-ep8.warm_start")
    job = manifest.load_module(cell.job_path).make_job(cell.config, jax.devices()[:1], 1)
    app = job.app_state(job.shapes, -1)
    assert sorted(app) == ["model", "optimizer", "progress"]
    assert sorted(app["model"].tree) == ["master", "params"]
    leaves = [x for s in app.values() for x in jax.tree.leaves(s.state_dict())]
    arrays = [x for x in leaves if hasattr(x, "shape")]
    assert len(leaves) == 278 and len(arrays) == 277  # and the step
    assert sum(nbytes(s) for s in arrays) == job.state_bytes == 9_038_726_148
    assert sorted(job.state_of(app)) == ["master", "opt", "params"]


def test_sdar_s_tree_is_what_it_was():
    check_sdar_s_tree_is_what_it_was(manifest.load_manifest())


# Every check above that reads the manifest, run again on a copy to
# which a later change's configuration, cell and per-layer entry are
# appended: none of them asks where an entry stands.
MANIFEST_CHECKS = [
    check_the_cell_resolves_to_files_that_exist,
    check_the_configuration_keeps_every_published_key,
    check_the_state_is_the_one_the_cell_is_for,
    check_sdar_s_tree_is_what_it_was,
]


@pytest.mark.parametrize("check", MANIFEST_CHECKS, ids=lambda c: c.__name__)
def test_every_manifest_check_holds_once_a_later_pr_has_appended(check):
    check(manifest_of_a_later_pr())


# ------------------------------------------------- the job's contract, toy


def test_the_job_keeps_the_contract_the_harness_and_the_loops_use():
    """``perfbench/README.md``, "The job's contract", with the biases: a
    member of the state that the step moves and no gradient does."""
    cell, job = toy_job("toy-lfm2.save_in_loop")
    shapes = jax.tree.leaves(job.shapes)
    assert job.state_bytes == sum(nbytes(s) for s in shapes)

    def same_kind(tree):
        assert jax.tree.structure(tree) == jax.tree.structure(job.shapes)
        for leaf, s in zip(jax.tree.leaves(tree), shapes):
            assert (leaf.shape, leaf.dtype) == (s.shape, s.dtype)
            assert set(leaf.sharding.device_set) <= set(job.devices)

    state = job.init_state()
    same_kind(state)
    checksum = reference.make_checksum_fn()
    np.testing.assert_array_equal(checksum(state), checksum(job.init_state()))
    zeros = job.template(cell.traffic.get("check_layout"))
    same_kind(zeros)
    assert not np.asarray(checksum(zeros)).any()
    assert job.tokens(3).shape == (2, 16) and int(np.max(job.tokens(3))) < 64
    app = job.app_state(state, 7)
    assert sorted(app) == ["model", "optimizer", "progress"]
    assert sorted(app["model"].tree) == ["master", "params"]
    assert job.step_of(app) == 7
    assert all(
        a is b for a, b in zip(jax.tree.leaves(job.state_of(app)), jax.tree.leaves(state))
    )
    stepped, loss = job.train_step(state, 0)
    assert isinstance(loss, float) and np.isfinite(loss)
    same_kind(stepped)
    _, loss_again = job.train_step(job.init_state(), 0)
    assert loss_again == loss
    moved = (np.asarray(checksum(stepped)) != np.asarray(checksum(job.init_state()))).any(1)
    for name, has_moved, s in zip(reference.leaf_names(job.shapes), moved, shapes):
        if not name.startswith("['params']") or len(s.shape) >= 2:
            assert has_moved, name
    # each bias moved by the rate, towards the mean load, from zero
    for bias in stepped["params"]["expert_bias"]:
        rate = np.float32(0.001)
        assert set(np.unique(np.asarray(bias)).tolist()) <= {-rate, 0.0, rate}
        assert np.asarray(bias).any()


@pytest.mark.parametrize(
    "change,match",
    [
        ({"mesh": {"dp": 1, "tp": 4}}, "mesh must be null"),
        ({"conv_bias": True}, "without bias"),
        ({"use_expert_bias": False}, "expert bias"),
        ({"tie_word_embeddings": False}, "ties the head"),
        ({"num_experts": 5}, "one id each"),
        ({"optimizer": {"name": "sgd"}}, "AdamW only"),
    ],
)
def test_a_configuration_the_model_cannot_run_is_refused_aloud(change, match):
    cell = manifest.resolve_cell(toy_manifest(), "toy-lfm2.save_in_loop")
    with pytest.raises(ValueError, match=match):
        manifest.load_module(cell.job_path).make_job(
            dict(cell.config, **change), jax.devices()[:1], 1
        )


# --------------------------------------------------- the loops, toy size


@pytest.mark.parametrize("name", sorted(TOY_CELLS))
def test_both_loop_kinds_run_end_to_end_under_the_job(name, dirs):
    line = run_toy(name, dirs)
    assert line["correct"] is True, line
    cell = manifest.resolve_cell(toy_manifest(), name)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["compiles_in_window"] == 0
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert os.listdir(dirs["roots_parent"]) == []


@pytest.mark.parametrize("fits", [True, False], ids=["clones_fit", "clones_do_not"])
def test_a_traced_run_reads_the_capture_s_headroom(dirs, monkeypatch, fits):
    """The device's memory faked (the CPU reports none): where the peak
    leaves room for the clones the save clones and the headroom reads
    positive; where it does not, the save stages to the host without an
    attempt, says so in the words the harness counts, and reads
    negative."""
    from torchsnapshot_tpu import io_preparer

    _, job = toy_job("toy-lfm2.save_in_loop")
    limit = 10**8
    peak = limit // 2 if fits else limit - job.state_bytes // 2
    monkeypatch.setattr(io_preparer, "_peak_and_limit", lambda device: (peak, limit))
    attempts = []
    real = io_preparer.device_clone
    monkeypatch.setattr(
        io_preparer, "device_clone", lambda arrays: attempts.append(1) or real(arrays)
    )
    line = run_toy("toy-lfm2.save_in_loop", dirs, trace=True)
    assert line["correct"] is True, line
    headroom = line["metrics"]["capture_device_headroom_share"]
    assert headroom["unit"] == "%"
    # the cell lists no staging threads' entry, and the line none
    assert not set(STAGING) & set(line["metrics"])
    saves = line["info"]["saves"]
    if fits:
        assert line["info"]["capture_fallbacks"] == 0 and len(attempts) >= saves
        assert headroom["value"] == pytest.approx(100 * (limit - peak - job.state_bytes) / limit)
        assert "capture_host_stage_ms" not in line["metrics"]
    else:
        # the warm-up's saves of one leaf a shape fit; the window's do not
        assert line["info"]["capture_fallbacks"] == saves >= 1
        assert headroom["value"] < 0
        assert line["metrics"]["capture_host_stage_ms"]["value"] > 0


def test_the_headroom_reader_on_recorded_counters_and_on_none(monkeypatch):
    from torchsnapshot_tpu import telemetry

    read = manifest.load_module(
        manifest.resolve_cell(manifest.load_manifest(), CELL).reader_paths[
            "capture_device_headroom_share"
        ]
    ).read
    saves = [{"step": 16, "blocked_s": 2.5, "durable_s": 11.0}]
    values = {
        "tpusnapshot_capture_clone_bytes": 7_961_069_828.0,
        "tpusnapshot_capture_device_peak_bytes": 10_600_000_000.0,
        "tpusnapshot_capture_device_limit_bytes": float(HBM),
    }
    monkeypatch.setattr(telemetry, "snapshot", lambda: dict(values))
    assert read({"saves": saves}) == pytest.approx(
        100 * (HBM - 10_600_000_000 - 7_961_069_828) / HBM
    )
    assert read({"saves": saves}) < 0 and read({}) is None and read({"saves": []}) is None
    # a device that reports no memory, and a program without the counters
    monkeypatch.setattr(
        telemetry, "snapshot",
        lambda: {**values, "tpusnapshot_capture_device_limit_bytes": 0.0},
    )
    assert read({"saves": saves}) is None
    monkeypatch.setattr(telemetry, "snapshot", lambda: {"tpusnapshot_takes_total": 3.0})
    assert read({"saves": saves}) is None
