"""Job ``nemotron_h_adam`` (``jobs/nemotron_h_adam.py``) and its
configuration: the cell of ``BENCHMARK.json`` resolved to files that
exist and sizes that are the published ones, the job held to the job's
contract at a toy size, both loop kinds end to end under it on the CPU,
what the control ``lossy_save`` rounds of its state, and a restore whose
template and landed arrays together pass a faked device budget.

The toy configuration (``data/configs/toy-nemotron.json``) comes in as
the toy cells of ``toy.py`` do: a file and entries in a copy of the
manifest.
"""

import json
import os
import time
import weakref

import jax
import numpy as np
import pytest

from perfbench import faults, harness, manifest, reference
from perfbench.tests.toy import manifest_of_a_later_pr, manifest_with

CELL = "nemotron3-nano-30b-a3b-ep16.save_in_loop"
TOY_CELLS = {
    "toy-nemotron.save_in_loop": ("toy-nemotron", "toy_save_in_loop", 1, CELL),
    "toy-nemotron.kill_resume": (
        "toy-nemotron", "toy_kill_resume", 1, "gpt3-6.7b.kill_resume",
    ),
}
# The published sizes (config.json of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16),
# written out: no width may differ in the file.
PUBLISHED_WIDTHS = {
    "hidden_size": 2688, "head_dim": 128, "num_attention_heads": 32,
    "num_key_value_heads": 2, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4, "chunk_size": 128,
    "expand": 2, "intermediate_size": 1856, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "num_experts_per_tok": 6,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5, "num_hidden_layers": 52,
}


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
    assert cell.chips == 1 and cell.traffic["loop"] == "save_in_loop"
    assert os.path.relpath(cell.job_path, manifest.CHECKOUT) == (
        "perfbench/jobs/nemotron_h_adam.py"
    )
    assert callable(manifest.load_module(cell.job_path).make_job)
    assert [x["name"] for x in cell.end_to_end] == ["loop_steps_per_s", "setup_s"]
    names = [x["name"] for x in cell.per_layer]
    like = [x["name"] for x in manifest.metrics_of(m, "per_layer", "gpt3-6.7b.save_in_loop")]
    own = ["capture_host_stage_ms", "capture_d2h_share"]
    assert [n for n in names if n not in own] == like
    assert set(names) - set(like) == set(own) and len(names) == len(like) + 2
    # twelve, and the device's idle gaps by the host's stage on both sides
    assert len(like) == 14
    assert {"save_idle_staging_ms", "save_idle_outside_library_ms"} <= set(like)
    for path in cell.reader_paths.values():
        assert os.path.isfile(path)
    # one save a window, checked on the job's own layout
    assert cell.traffic["save_every_steps"] >= 100000 and cell.traffic["keep"] == 2
    assert cell.traffic["check_layout"] is None and cell.config["mesh"] is None


def test_benchmark_resolves_the_new_cell_to_files_that_exist():
    check_the_cell_resolves_to_files_that_exist(manifest.load_manifest())


def check_the_configuration_keeps_every_published_width(m):
    cell = manifest.resolve_cell(m, CELL)
    config = cell.config
    assert {k: config[k] for k in PUBLISHED_WIDTHS} == PUBLISHED_WIDTHS
    assert config["reduced"] == ["layers_held", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "layers_held": 52, "n_routed_experts": 128, "vocab_size": 131072,
    }
    assert len(config["hybrid_override_pattern"]) == 52
    cfg = manifest.load_module(cell.job_path).model_config(config)
    assert cfg.pattern == "MEMEM*EME"
    assert cfg.n_routed_experts == 128 and cfg.expert_ids == tuple(range(8))
    assert cfg.vocab_size == 16384 == 131072 // 8
    with open(os.path.join(manifest.BENCH_DIR, "configs", "gpt3-6.7b.json")) as f:
        assert config["guarantees"] == json.load(f)["guarantees"]


def test_the_configuration_keeps_every_published_width():
    check_the_configuration_keeps_every_published_width(manifest.load_manifest())


def check_the_state_is_the_one_the_cell_is_for(m):
    """Sizes from shapes alone (nothing is allocated): 667M parameters at
    14 B saved, 289 leaves, 64 of them under 1 KB, the largest a float32
    embedding moment of 176 MB, the held experts as two stacked leaves."""
    cell = manifest.resolve_cell(m, CELL)
    job = manifest.load_module(cell.job_path).make_job(cell.config, jax.devices()[:1], 1)
    leaves = jax.tree.leaves(job.shapes)
    sizes = [int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize for s in leaves]
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(job.shapes["master"]))
    assert n_params == 666_963_456
    assert job.state_bytes == sum(sizes) == 14 * n_params + 4 == 9_337_488_388
    assert job.state_bytes > 16_909_336_064 // 2  # above HBM/2 of a v5e
    assert len(leaves) == 289 and min(sizes) == 4 and max(sizes) == 176_160_768
    assert sum(1 for s in sizes if s < 1024) == 64 + 1  # and the count
    experts = job.shapes["params"]["blocks"][1]
    assert experts["up"].shape == (8, 2688, 1856) and experts["down"].shape == (8, 1856, 2688)
    assert experts["router"].shape == (2688, 128)
    assert {str(s.dtype) for s in leaves} == {"bfloat16", "float32", "int32"}


def test_the_state_is_the_one_the_cell_is_for():
    check_the_state_is_the_one_the_cell_is_for(manifest.load_manifest())


# ------------------------------------------------- the job's contract, toy


def test_the_job_keeps_the_contract_the_harness_and_the_loops_use():
    """``perfbench/README.md``, "The job's contract", as
    ``test_manifest.py`` holds the toy jobs to it."""
    cell, job = toy_job("toy-nemotron.save_in_loop")
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
    # the count in every leaf, but for the correction bias, which has no
    # gradient (top k is not differentiable) and no weight decay; of the
    # bfloat16 copies every matrix (a norm's weight of 1 - 1e-3 rounds
    # back to 1)
    moved = (np.asarray(checksum(stepped)) != np.asarray(checksum(job.init_state()))).any(1)
    for name, has_moved, s in zip(reference.leaf_names(job.shapes), moved, shapes):
        if "router_bias" in name:
            assert not has_moved, name
        elif not name.startswith("['params']") or len(s.shape) >= 2:
            assert has_moved, name


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


def test_a_traced_run_reads_the_capture_when_nothing_can_be_cloned(dirs, monkeypatch):
    """A chip too full to clone, faked: ``device_clone`` gives up, the
    library says so in the words the harness counts, stages to the host
    inside ``async_save``, and both new readers find their span."""
    from torchsnapshot_tpu import io_preparer

    name = "toy-nemotron.save_in_loop"
    cloned = run_toy(name, dirs, trace=True)
    assert cloned["correct"] is True, cloned
    assert cloned["info"]["capture_fallbacks"] == 0
    assert "capture_host_stage_ms" not in cloned["metrics"]  # left out, not 0
    assert "capture_d2h_share" not in cloned["metrics"]

    monkeypatch.setattr(io_preparer, "device_clone", lambda arrays: None)
    staged = run_toy(name, dirs, trace=True)
    assert staged["correct"] is True, staged
    assert staged["info"]["capture_fallbacks"] >= 1
    assert staged["metrics"]["capture_fallbacks"]["value"] >= 1
    assert staged["metrics"]["capture_host_stage_ms"]["value"] > 0
    assert staged["metrics"]["capture_d2h_share"]["value"] > 0
    listed = manifest.resolve_cell(toy_manifest(), name).per_layer
    named = {m["name"] for m in listed}
    traced = {m["name"] for m in listed if m["source"] == "device_trace"}
    assert "device_idle_pct.save" in traced
    assert set(staged["metrics"]) == named - traced  # no device plane here


def check_the_new_readers_return_nothing_where_the_program_has_no_such_span(m):
    """On the parent of this PR the span does not exist: the readers say
    None and the line leaves the metrics out."""
    cell = manifest.resolve_cell(m, CELL)
    obs = {
        "saves": [{"step": 3, "blocked_s": 0.1, "durable_s": 2.0}],
        "state_bytes": 10**9,
        "probes": {"d2h_gbps": 10.0},
        "spans": {"write": [(0.0, 1.0)], "stage": [(0.0, 0.5)]},
    }
    for name in ("capture_host_stage_ms", "capture_d2h_share"):
        read = manifest.load_module(cell.reader_paths[name]).read
        assert read(obs) is None and read({}) is None and read({**obs, "spans": None}) is None
    with_span = {**obs, "spans": {"capture_host_stage": [(1.0, 1.5)]}}
    read = manifest.load_module(cell.reader_paths["capture_host_stage_ms"]).read
    assert read(with_span) == pytest.approx(500.0)
    read = manifest.load_module(cell.reader_paths["capture_d2h_share"]).read
    assert read(with_span) == pytest.approx(20.0)  # 2 GB/s of 10
    assert read({**with_span, "probes": None}) is None


def test_the_new_readers_return_nothing_where_the_program_has_no_such_span():
    m = manifest.load_manifest()
    check_the_new_readers_return_nothing_where_the_program_has_no_such_span(m)


# Every check above that reads the manifest, run again on a copy to
# which a later change's configuration, cell and per-layer entry are
# appended: none of them asks where an entry stands.
MANIFEST_CHECKS = [
    check_the_cell_resolves_to_files_that_exist,
    check_the_configuration_keeps_every_published_width,
    check_the_state_is_the_one_the_cell_is_for,
    check_the_new_readers_return_nothing_where_the_program_has_no_such_span,
]


@pytest.mark.parametrize("check", MANIFEST_CHECKS, ids=lambda c: c.__name__)
def test_every_manifest_check_holds_once_a_later_pr_has_appended(check):
    check(manifest_of_a_later_pr())


# ----------------------------------------------------------- the control


def test_which_leaves_lossy_save_rounds_and_which_it_passes(dirs, capsys):
    """The control rounds leaves of two or more axes: every matrix and
    every stacked expert leaf, in all four copies (bfloat16 to float8,
    float32 to bfloat16). It passes the vectors (norms, ``A_log``, ``D``,
    ``dt_bias``, the convolution's and the router's bias) and the count."""
    with faults.FAULTS["lossy_save"]():
        line = run_toy("toy-nemotron.save_in_loop", dirs)
    assert line["correct"] is False, line
    said = [
        json.loads(ln.split("MISMATCH ", 1)[1])
        for ln in capsys.readouterr().out.splitlines()
        if "MISMATCH" in ln
    ]
    _, job = toy_job("toy-nemotron.save_in_loop")
    flat, _ = jax.tree_util.tree_flatten_with_path(job.shapes)
    rounded = {jax.tree_util.keystr(p) for p, s in flat if len(s.shape) >= 2}
    assert {d["leaf"] for d in said} == rounded
    per_copy = {"M": 3, "E": 5, "*": 4}  # in_proj conv_w out_proj | router up down shared x2 | q k v o
    assert len(rounded) == 4 * (sum(per_copy[k] for k in job.cfg.pattern) + 2)
    assert line["compared"]["leaves_differing"]["value"] == len(rounded) * len(
        {d["step"] for d in said}
    )
    assert "['params']['embed']" in rounded and "['opt'][1]" not in rounded


# ------------------------------------------- a restore above the budget


def test_a_restore_whose_template_and_landed_arrays_pass_the_budget_succeeds(
    tmp_path, monkeypatch
):
    """A device of 1.5 x the state, faked: what the library reads as
    free is that budget less the bytes of every live array. Template +
    landed arrays (2 x) do not fit, the state alone does. The library
    lets each Stateful's template go before it reads (none of its leaves
    is alive when its reads start), lands every leaf bit for bit, never
    holds more live bytes than the budget, and says in the restore's
    report what it released. On a device with room for both it releases
    nothing."""
    from torchsnapshot_tpu import CheckpointManager, io_preparer
    from torchsnapshot_tpu import snapshot as snapshot_mod

    _, job = toy_job("toy-nemotron.save_in_loop")
    checksum = reference.make_checksum_fn()
    state, _ = job.train_step(job.init_state(), 0)
    pinned = np.asarray(checksum(state))
    base = str(tmp_path / "ckpt")
    CheckpointManager(base).save(4, job.app_state(state, 4))
    del state

    def live_bytes():
        return sum(x.nbytes for x in jax.live_arrays())

    others = live_bytes()  # whatever else this process holds
    budget = [0]
    monkeypatch.setattr(
        io_preparer,
        "_device_free_bytes",
        lambda device=None: max(0, budget[0] - (live_bytes() - others)),
    )
    seen = []  # (template leaves of this Stateful alive, live bytes) at each read
    real = snapshot_mod.execute_read_reqs

    async def spying(read_reqs, *args, **kwargs):
        seen.append((sum(ref() is not None for ref in template_leaves), live_bytes() - others))
        return await real(read_reqs, *args, **kwargs)

    monkeypatch.setattr(snapshot_mod, "execute_read_reqs", spying)

    def report():
        with open(os.path.join(base, "step-4", ".report.restore.json")) as f:
            return json.load(f)["ranks"][0]

    n_opt = len(jax.tree.leaves(job.shapes["opt"]))
    budget[0] = job.state_bytes * 3 // 2
    target = job.app_state(job.template(), -1)
    template_leaves = [weakref.ref(x) for x in jax.tree.leaves(job.state_of(target))]
    assert live_bytes() - others == job.state_bytes
    assert CheckpointManager(base).restore(target) == 4
    assert job.step_of(target) == 4
    np.testing.assert_array_equal(np.asarray(checksum(job.state_of(target))), pinned)
    # model, optimizer, progress in turn: at the model's reads only the
    # optimizer's template is left, at the optimizer's nothing
    assert [alive for alive, _ in seen] == [n_opt, 0, 0]
    assert max(live for _, live in seen) <= job.state_bytes < budget[0]
    assert report()["template_released_bytes"] == job.state_bytes
    assert "device_peak_bytes" in report()
    del target

    seen.clear()
    budget[0] = job.state_bytes * 8
    target = job.app_state(job.template(), -1)
    template_leaves = [weakref.ref(x) for x in jax.tree.leaves(job.state_of(target))]
    assert CheckpointManager(base).restore(target) == 4
    np.testing.assert_array_equal(np.asarray(checksum(job.state_of(target))), pinned)
    assert seen[0][0] == len(template_leaves)  # kept until its replacement landed
    assert report()["template_released_bytes"] == 0
