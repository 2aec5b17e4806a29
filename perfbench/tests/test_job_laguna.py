"""Job ``laguna_adam`` (``jobs/laguna_adam.py``) and its configuration:
the cell of ``BENCHMARK.json`` resolved to files that exist and sizes
that are the published ones, the state's exact counts, the job held to
the job's contract at a toy size, both loop kinds end to end under it on
the CPU, and the two readers of what a restore above HBM/2 records
(``layers/restore_template_release_ms.py``,
``layers/restore_device_wait_thread_s.py``).

The toy configuration (``data/configs/toy-laguna.json``) comes in as the
toy cells of ``toy.py`` do: a file and entries in a copy of the manifest.
"""

import json
import os
import time

import jax
import numpy as np
import pytest

from perfbench import harness, manifest, reference
from perfbench.tests.toy import manifest_of_a_later_pr, manifest_with

CELL = "laguna-xs2-ep8.kill_resume"
TOY_CELLS = {
    "toy-laguna.save_in_loop": (
        "toy-laguna", "toy_save_in_loop", 1, "nemotron3-nano-30b-a3b-ep16.save_in_loop",
    ),
    "toy-laguna.kill_resume": ("toy-laguna", "toy_kill_resume", 1, CELL),
}
# The published sizes (config.json of poolside/Laguna-XS.2), written
# out: no width may differ in the file.
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 8192, "head_dim": 128,
    "num_attention_heads": 48, "num_key_value_heads": 8, "sliding_window": 512,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "num_experts_per_tok": 8, "moe_routed_scaling_factor": 2.5,
    "num_hidden_layers": 40, "max_position_embeddings": 262144,
    "rms_norm_eps": 1e-6, "partial_rotary_factor": 0.5,
}
PUBLISHED_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
        "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5,
    },
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1,
    },
    "original_max_position_embeddings": 4096,
}
HBM = 16_909_336_064  # a v5e's bytes_limit


def toy_manifest():
    return manifest_with(TOY_CELLS)


def toy_job(name, seed=2**31 + 11):
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


# ------------------------------------------------- the cell, as accepted


def check_the_cell_resolves_to_files_that_exist(m):
    cell = manifest.resolve_cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "kill_resume"
    assert cell.traffic["loop"] == "kill_resume" and cell.traffic["warm_steps"] == 3
    assert os.path.relpath(cell.job_path, manifest.CHECKOUT) == (
        "perfbench/jobs/laguna_adam.py"
    )
    assert callable(manifest.load_module(cell.job_path).make_job)
    assert [x["name"] for x in cell.end_to_end] == ["resume_s", "setup_s"]
    # the accepted resume metrics that list their cells by name; the
    # seven restore-phase metrics of PR 30 stay with the GPT-3 cell
    assert [x["name"] for x in cell.per_layer] == [
        "restore_h2d_share", "read_busy_share", "first_step_after_restore_ms",
        "device_idle_pct.resume",
        # the two readers of the template let go and of the device
        # budget's waits, and the device's idle gaps by the host's stage
        "restore_template_release_ms", "restore_device_wait_thread_s",
        "resume_idle_h2d_ms", "resume_idle_consume_ms", "resume_idle_read_ms",
        "resume_idle_outside_pipeline_ms",
    ]
    for path in cell.reader_paths.values():
        assert os.path.isfile(path)
    assert cell.config["mesh"] is None and cell.config["save_options"] == {}
    # one configuration file a configuration
    files = [c["file"] for c in m["configs"]]
    assert files.count("perfbench/configs/laguna-xs2-ep8.json") == 1


def test_benchmark_resolves_the_new_cell_to_files_that_exist():
    check_the_cell_resolves_to_files_that_exist(manifest.load_manifest())


def check_the_configuration_keeps_every_published_key(m):
    cell = manifest.resolve_cell(m, CELL)
    config = cell.config
    assert {k: config[k] for k in PUBLISHED_WIDTHS} == PUBLISHED_WIDTHS
    assert config["rope_parameters"] == PUBLISHED_ROPE
    assert config["model_type"] == "laguna" and config["gating"] is True
    assert config["attention_bias"] is False and config["tie_word_embeddings"] is False
    assert config["moe_apply_router_weight_on_input"] is False
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert config["layer_types"] == period * 10
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert config["reduced"] == ["layers_held", "num_experts", "vocab_size"]
    assert config["published"] == {
        "layers_held": 40, "num_experts": 256, "vocab_size": 100352,
    }
    assert (config["layers_held"], config["num_experts"], config["vocab_size"]) == (
        5, 32, 12544,
    )
    assert config["vocab_size"] * 8 == 100352 and config["num_experts"] * 8 == 256
    for key in ("deployment", "assumed", "guarantees", "source"):
        assert config[key]
    with open(os.path.join(manifest.BENCH_DIR, "configs", "gpt3-6.7b.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]
    cfg = manifest.load_module(cell.job_path).model_config(config)
    # the floor: the leading dense layer and a whole period after it
    assert cfg.layer_types == tuple(period + ["full_attention"])
    assert cfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert cfg.num_attention_heads_per_layer == (48, 64, 64, 64, 48)
    assert cfg.num_experts == 256 and cfg.expert_ids == tuple(range(32))
    assert cfg.rope_full.attention_factor == 1.4158883083359672
    assert cfg.rope_full.partial_rotary_factor == 0.5 and cfg.rope_full.factor == 64
    assert cfg.rope_sliding.factor is None and cfg.rope_sliding.theta == 10000
    # every seed the same work: the held experts run dense, never a data-dependent branch
    assert cfg.flash_attention and cfg.expert_capacity == 0 and cfg.expert_dense_group == 8


def test_the_configuration_keeps_every_published_key():
    check_the_configuration_keeps_every_published_key(manifest.load_manifest())


def check_the_state_is_the_one_the_cell_is_for(m):
    """Sizes from shapes alone (nothing is allocated): 692M parameters at
    14 B saved, above HBM/2, 241 leaves, a layer's leaves unlike by kind,
    the held experts as two fused stacked leaves, the largest 268 MB."""
    cell = manifest.resolve_cell(m, CELL)
    job = manifest.load_module(cell.job_path).make_job(cell.config, jax.devices()[:1], 1)
    leaves = jax.tree.leaves(job.shapes)
    sizes = [int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize for s in leaves]
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    n_params = count(job.shapes["master"])
    assert n_params == 691_623_936
    assert [count(blk) for blk in job.shapes["master"]["layers"]] == [
        79_794_176, 142_217_216, 142_217_216, 142_217_216, 133_795_840,
    ]
    assert job.state_bytes == sum(sizes) == 14 * n_params + 4 == 9_682_735_108
    assert 16 * n_params == 11_065_982_976  # resident with a step's gradients
    assert HBM // 2 < job.state_bytes and 0.57 < job.state_bytes / HBM < 0.58
    assert len(leaves) == 4 * (3 + 9 + 4 * 12) + 1 == 241
    assert min(sizes) == 4 and max(sizes) == 268_435_456
    assert sum(1 for s in sizes if s > 64 * 2**20) == 37  # read as 64 MiB parts
    assert len({(s.shape, str(s.dtype)) for s in leaves}) == 31
    assert {str(s.dtype) for s in leaves} == {"bfloat16", "float32", "int32"}
    full, window = (job.shapes["params"]["layers"][i] for i in (0, 1))
    assert full["wq"].shape == (2048, 48 * 128) and window["wq"].shape == (2048, 64 * 128)
    assert full["wg"].shape == (2048, 48) and window["wg"].shape == (2048, 64)
    assert full["wk"].shape == window["wv"].shape == (2048, 8 * 128)
    assert full["gate_up"].shape == (2048, 2 * 8192) and full["down"].shape == (8192, 2048)
    assert window["gate_up"].shape == (32, 2048, 1024)
    assert window["down"].shape == (32, 512, 2048)
    assert window["router"].shape == (2048, 256)
    assert window["shared_gate_up"].shape == (2048, 1024)
    assert job.shapes["params"]["embed"].shape == (12544, 2048)
    assert job.shapes["params"]["head"].shape == (2048, 12544)
    assert (job.batch, job.seq_len) == (1, 8192)


def test_the_state_is_the_one_the_cell_is_for():
    check_the_state_is_the_one_the_cell_is_for(manifest.load_manifest())


# Every check above that reads the manifest, run again on a copy to
# which a later change's configuration, cell and per-layer entry are
# appended: none of them asks where an entry stands.
MANIFEST_CHECKS = [
    check_the_cell_resolves_to_files_that_exist,
    check_the_configuration_keeps_every_published_key,
    check_the_state_is_the_one_the_cell_is_for,
]


@pytest.mark.parametrize("check", MANIFEST_CHECKS, ids=lambda c: c.__name__)
def test_every_manifest_check_holds_once_a_later_pr_has_appended(check):
    check(manifest_of_a_later_pr())


# ------------------------------------------------- the job's contract, toy


def test_the_job_keeps_the_contract_the_harness_and_the_loops_use():
    """``perfbench/README.md``, "The job's contract", as
    ``test_manifest.py`` holds the toy jobs to it."""
    cell, job = toy_job("toy-laguna.kill_resume")
    shapes = jax.tree.leaves(job.shapes)
    assert all(isinstance(s, jax.ShapeDtypeStruct) for s in shapes)
    assert all(np.dtype(s.dtype).itemsize in (1, 2, 4) for s in shapes)
    assert job.state_bytes == sum(
        int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize for s in shapes
    )
    assert list(job.devices) == jax.devices()[:1]

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
    zeros = job.template(cell.traffic.get("check_layout"))
    same_kind(zeros)
    assert not np.asarray(checksum(zeros)).any()
    with pytest.raises(ValueError, match="one layout"):
        job.template({"dp": 2, "tp": 2})
    np.testing.assert_array_equal(job.tokens(3), job.tokens(3))
    assert (np.asarray(job.tokens(3)) != np.asarray(job.tokens(4))).any()
    assert int(np.max(job.tokens(3))) < job.cfg.vocab_size  # ids from the slice
    app = job.app_state(state, 7)
    assert sorted(app) == ["model", "optimizer", "progress"]
    assert all(callable(s.state_dict) and callable(s.load_state_dict) for s in app.values())
    assert job.step_of(app) == 7
    assert all(
        a is b for a, b in zip(jax.tree.leaves(job.state_of(app)), jax.tree.leaves(state))
    )
    few = job.app_state({"leaf0": jax.tree.leaves(state)[0]}, 0)
    held = [
        x for s in few.values() for x in jax.tree.leaves(s.state_dict())
        if hasattr(x, "shape")
    ]
    assert len(held) == 1 and held[0] is jax.tree.leaves(state)[0]
    stepped, loss = job.train_step(state, 0)
    assert isinstance(loss, float) and np.isfinite(loss)
    same_kind(stepped)
    _, loss_again = job.train_step(again, 0)
    assert loss_again == loss
    # the step moved every part of the state: master, both moments and
    # the count in every leaf; of the bfloat16 copies every matrix (a
    # norm's weight of 1 - 1e-3 rounds back to 1)
    moved = (np.asarray(checksum(stepped)) != np.asarray(checksum(job.init_state()))).any(1)
    for name, has_moved, s in zip(reference.leaf_names(job.shapes), moved, shapes):
        if not name.startswith("['params']") or len(s.shape) >= 2:
            assert has_moved, name


@pytest.mark.parametrize(
    "change,match",
    [
        ({"mesh": {"dp": 1, "tp": 4}}, "mesh must be null"),
        ({"attention_bias": True}, "no bias"),
        ({"gating": False}, "gates each head"),
        ({"num_experts": 5}, "one id each"),
        ({"optimizer": {"name": "sgd"}}, "AdamW only"),
    ],
)
def test_a_configuration_the_model_cannot_run_is_refused_aloud(change, match):
    cell = manifest.resolve_cell(toy_manifest(), "toy-laguna.kill_resume")
    with pytest.raises(ValueError, match=match):
        manifest.load_module(cell.job_path).make_job(
            dict(cell.config, **change), jax.devices()[:1], 1
        )


# ------------------------------------------------------ both loops, toy


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
    if name.endswith("kill_resume"):
        assert line["info"]["resumed_losses_equal"] is True


# ------------------------------------- the two readers of a crowded restore

READERS = ("restore_template_release_ms", "restore_device_wait_thread_s")


def _reader(name):
    return manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "layers", name + ".py")
    ).read


def test_a_traced_resume_under_a_faked_device_budget_reads_both(dirs, monkeypatch):
    """A device with room for next to nothing, by the library's own knob:
    every cycle's restore lets its templates go, the harness keeps the
    program's spans, and both readers find theirs. With room for
    everything neither span exists and both say None."""
    name = "toy-laguna.kill_resume"
    kept = {}
    real = harness.Run._read_trace

    def keeping(self, device_doc):
        out = real(self, device_doc)
        kept.update(self.obs)
        return out

    monkeypatch.setattr(harness.Run, "_read_trace", keeping)
    roomy = run_toy(name, dirs, trace=True)
    assert roomy["correct"] is True, roomy
    assert "restore.plan" in kept["spans"]
    assert all(_reader(r)(kept) is None for r in READERS)

    monkeypatch.setenv("TPUSNAPSHOT_DEVICE_BUDGET_BYTES", "4096")
    kept.clear()
    crowded = run_toy(name, dirs, trace=True)
    assert crowded["correct"] is True, crowded
    cycles = len(kept["cycles"])
    # model and optimizer each release, every cycle
    assert len(kept["spans"]["restore.release_template"]) == 2 * cycles
    assert _reader("restore_template_release_ms")(kept) > 0
    assert _reader("restore_device_wait_thread_s")(kept) >= 0
    # the metrics the cell lists are all there but the device trace's
    # (no device plane on the CPU backend)
    listed = manifest.resolve_cell(toy_manifest(), name).per_layer
    named = {m["name"] for m in listed}
    traced = {m["name"] for m in listed if m["source"] == "device_trace"}
    assert "device_idle_pct.resume" in traced
    assert set(crowded["metrics"]) == named - traced


def test_the_readers_on_recorded_spans_and_on_none():
    cycles = [{"restore_s": 1.0, "first_step_s": 0.1}] * 2
    release = [(0.10, 0.14), (0.20, 0.22), (1.10, 1.14), (1.20, 1.22)]
    waits = [(0.5, 0.9), (0.6, 0.9), (1.5, 1.6)]
    plan = [(0.0, 0.3), (1.0, 1.3)]
    both = {"restore.plan": plan, "restore.release_template": release,
            "restore.device_budget_wait": waits}
    obs = {"cycles": cycles, "spans": both}
    assert _reader("restore_template_release_ms")(obs) == pytest.approx(60.0)
    assert _reader("restore_device_wait_thread_s")(obs) == pytest.approx(0.4)
    # the program has the spans and no admission waited: 0, not None
    none_fired = {"cycles": cycles, "spans": {k: v for k, v in both.items() if "wait" not in k}}
    assert _reader("restore_device_wait_thread_s")(none_fired) == 0.0
    # waits without a release (a template that fitted, a budget that did not)
    only_waits = {"cycles": cycles, "spans": {"restore.plan": plan, "restore.device_budget_wait": waits}}
    assert _reader("restore_device_wait_thread_s")(only_waits) == pytest.approx(0.4)
    assert _reader("restore_template_release_ms")(only_waits) is None
    # an untraced run, a program without the spans (the parent commit),
    # a run of the other loop kind: nothing to read
    parent = {"restore.plan": plan, "read": [(0.3, 0.9)], "consume.verify": [(0.4, 0.5)]}
    for nothing in (
        {"cycles": cycles},
        {"cycles": cycles, "spans": {}},
        {"cycles": cycles, "spans": None},
        {"cycles": cycles, "spans": parent},
        {"saves": [{}], "spans": both},
        {},
    ):
        for name in READERS:
            assert _reader(name)(nothing) is None, (name, nothing)
