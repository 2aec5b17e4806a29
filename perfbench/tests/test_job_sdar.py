"""Job ``mixed_adamw`` (``jobs/mixed_adamw.py``) over ``models/sdar.py``,
its configuration, and the loop kind ``warm_start``
(``loops/warm_start.py``): the cell of ``BENCHMARK.json`` resolved to
files that exist and sizes that are the published ones, the state's
exact counts, the job held to the job's contract at a toy size, the
loop end to end under this job and under the toy job of a second kind,
every planted fault (and a restore that writes what it was not given)
turning ``correct`` false through it, and the two readers of what the
loop records (``layers/restore_selected_h2d_share.py``,
``layers/fresh_state_ms.py``).

The toy configuration (``data/configs/toy-sdar.json``) and the toy
traffic (``data/traffic/toy_warm_start*.json``) come in as the toy cells
of ``toy.py`` do: files and entries in a copy of the manifest.
"""

import contextlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import faults, harness, manifest, reference
from perfbench.tests.toy import manifest_of_a_later_pr, manifest_with
from torchsnapshot_tpu import CheckpointManager

CELL = "sdar-30b-a3b-ep8.warm_start"
TOY_CELLS = {
    "toy-sdar.warm_start": ("toy-sdar", "toy_warm_start", 1, CELL),
    "toy-sdar.kill_resume": ("toy-sdar", "toy_kill_resume", 1, CELL),
    "toy-sdar.save_in_loop": (
        "toy-sdar", "toy_save_in_loop", 1, "nemotron3-nano-30b-a3b-ep16.save_in_loop",
    ),
    # the loop under the toy job of a second kind, whose app state is
    # spelled otherwise: everything in one Stateful, the step in "clock"
    "toy-mixed.warm_start": ("toy-mixed", "toy_warm_start_everything", 1, CELL),
    "toy-mixed.warm_start_with_clock": ("toy-mixed", "toy_warm_start_with_clock", 1, CELL),
}
# The catalog's ``config`` of SDAR-30B-A3B-Chat (config.json of
# JetLM/SDAR-30B-A3B-Chat), written out: every key is in the file at
# this value but the two in ``reduced`` that it names.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "sdar_moe", "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
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


def run_toy(name, dirs, seed=5, seconds=1.0, trace=False, m=None):
    cell = manifest.resolve_cell(m or toy_manifest(), name)
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
    assert cell.chips == 1 and cell.traffic_name == "warm_start"
    assert cell.traffic["loop"] == "warm_start" and cell.traffic["warm_steps"] == 3
    assert cell.traffic["restore_statefuls"] == ["model"]
    assert cell.traffic["profile_at_cycle"] == 1
    rel = lambda path: os.path.relpath(path, manifest.CHECKOUT)
    assert rel(cell.job_path) == "perfbench/jobs/mixed_adamw.py"
    assert rel(cell.loop_path) == "perfbench/loops/warm_start.py"
    assert callable(manifest.load_module(cell.job_path).make_job)
    assert callable(manifest.load_module(cell.loop_path).run)
    assert [x["name"] for x in cell.end_to_end] == ["resume_s", "setup_s"]
    # the accepted resume metrics that list their cells by name, but
    # ``restore_h2d_share``, whose reader divides the whole state's bytes
    # where this cell lands a part; PR 30's seven stay with the GPT-3 cell
    assert [x["name"] for x in cell.per_layer] == [
        "read_busy_share", "first_step_after_restore_ms", "device_idle_pct.resume",
        # the two readers of what this loop records, and the device's
        # idle gaps by the host's stage
        "restore_selected_h2d_share", "fresh_state_ms",
        "resume_idle_h2d_ms", "resume_idle_consume_ms", "resume_idle_read_ms",
        "resume_idle_outside_pipeline_ms",
    ]
    for path in cell.reader_paths.values():
        assert os.path.isfile(path)
    (share,) = [x for x in m["per_layer"] if x["name"] == "restore_h2d_share"]
    assert CELL not in share["workloads"]
    assert cell.config["mesh"] is None and cell.config["save_options"] == {}
    assert cell.config["job"] == "mixed_adamw" and cell.config["model"] == "sdar"
    files = [c["file"] for c in m["configs"]]
    assert files.count("perfbench/configs/sdar-30b-a3b-ep8.json") == 1
    # the configuration and the cell stand once each, wherever they stand
    (config,) = [c for c in m["configs"] if c["name"] == "sdar-30b-a3b-ep8"]
    assert [w["name"] for w in m["workloads"]].count(CELL) == 1
    assert config["source"] == cell.config["source"]


def test_benchmark_resolves_the_new_cell_to_files_that_exist():
    check_the_cell_resolves_to_files_that_exist(manifest.load_manifest())


def check_the_configuration_keeps_every_published_key(m):
    cell = manifest.resolve_cell(m, CELL)
    config = cell.config
    reduced = {"num_experts": 16, "vocab_size": 18992}
    assert {k: config[k] for k in PUBLISHED} == {**PUBLISHED, **reduced}
    assert config["reduced"] == ["layers_held", "num_experts", "vocab_size"]
    assert config["published"] == {
        "layers_held": 48, "num_experts": 128, "vocab_size": 151936,
    }
    assert config["layers_held"] == 6 and config["expert_ids"] == list(range(16))
    assert config["vocab_size"] * 8 == 151936 and config["num_experts"] * 8 == 128
    assert config["layers_held"] >= 4  # the floor: the pattern's period is one layer
    for key in ("deployment", "source"):
        assert config[key]
    for key in ("block_length", "schedule", "time_a_block", "labels", "mask_id",
                "qk_norm", "doubled_sequence", "initial_values"):
        assert config["assumed"][key], key
    with open(os.path.join(manifest.BENCH_DIR, "configs", "laguna-xs2-ep8.json")) as f:
        theirs = json.load(f)["guarantees"]
    assert {k: config["guarantees"][k] for k in theirs} == theirs  # unweakened
    assert set(config["guarantees"]) == set(theirs) | {"subset"}
    assert "writes nothing outside" in config["guarantees"]["subset"]
    cfg = manifest.load_module(cell.job_path).make_job(
        config, jax.devices()[:1], 1
    ).cfg
    assert (cfg.hidden_size, cfg.layers, cfg.vocab_size) == (2048, 6, 18992)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (32, 4, 128)
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6
    assert cfg.num_experts == 128 and cfg.expert_ids == tuple(range(16))
    assert cfg.num_experts_per_tok == 8 and cfg.moe_intermediate_size == 768
    assert cfg.norm_topk_prob and cfg.routing.scoring == "softmax"
    assert cfg.routing.scaling_factor == 1.0 and cfg.routing.normalise
    assert cfg.block_length == 4 and cfg.mask_token_id == 18991
    assert cfg.flash_attention and cfg.expert_capacity == 1024 == 2 * 8192 * 8 // 128
    assert cfg.expert_dense_group == 8 and cfg.remat


def test_the_configuration_keeps_every_published_key():
    check_the_configuration_keeps_every_published_key(manifest.load_manifest())


def check_the_state_is_the_one_the_cell_is_for(m):
    """Sizes from shapes alone (nothing is allocated): 645.6M parameters
    at 14 B saved, above HBM/2, 277 leaves, of which the ``model``
    Stateful (what the cell restores) holds 138 and 3.87 GB; the held
    experts as two stacked leaves a layer, the largest 201 MB."""
    cell = manifest.resolve_cell(m, CELL)
    job = manifest.load_module(cell.job_path).make_job(cell.config, jax.devices()[:1], 1)
    leaves = jax.tree.leaves(job.shapes)
    nbytes = lambda s: int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
    sizes = [nbytes(s) for s in leaves]
    count = lambda tree: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    n_params = count(job.shapes["master"])
    assert n_params == 645_623_296
    assert [count(blk) for blk in job.shapes["master"]["layers"]] == [94_638_336] * 6
    blk = job.shapes["master"]["layers"][0]
    attention = count({k: blk[k] for k in
                       ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "mlp_norm")})
    assert attention == 18_878_720 and count(blk["router"]) == 262_144
    assert count(blk["gate_up"]) + count(blk["down"]) == 75_497_472
    assert n_params - 6 * 94_638_336 == 77_793_280  # embedding, head, final norm
    assert job.state_bytes == sum(sizes) == 14 * n_params + 4 == 9_038_726_148
    assert 16 * n_params == 10_329_972_736  # resident with a step's gradients
    assert HBM // 2 < job.state_bytes and 0.53 < job.state_bytes / HBM < 0.54
    assert len(leaves) == 4 * (3 + 6 * 11) + 1 == 277
    assert min(sizes) == 4 and max(sizes) == 201_326_592
    assert sum(1 for s in sizes if s > 64 * 2**20) == 50  # read as 64 MiB parts
    assert len({(s.shape, str(s.dtype)) for s in leaves}) == 21
    assert {str(s.dtype) for s in leaves} == {"bfloat16", "float32", "int32"}
    # what the cell restores: the ``model`` Stateful, 6 B a parameter
    app = job.app_state(job.shapes, -1)
    model = jax.tree.leaves(app["model"].tree)
    assert len(model) == 2 * 69 == 138
    assert sum(nbytes(s) for s in model) == 6 * n_params == 3_873_739_776
    assert len(jax.tree.leaves(app["optimizer"].tree)) == 139
    assert job.state_bytes - 3_873_739_776 == 5_164_986_372  # stays fresh
    params = job.shapes["params"]["layers"][0]
    assert params["wq"].shape == (2048, 32 * 128) and params["wo"].shape == (32 * 128, 2048)
    assert params["wk"].shape == params["wv"].shape == (2048, 4 * 128)
    assert params["q_norm"].shape == params["k_norm"].shape == (128,)
    assert params["gate_up"].shape == (16, 2048, 2 * 768)
    assert params["down"].shape == (16, 768, 2048)
    assert params["router"].shape == (2048, 128)
    assert job.shapes["params"]["embed"].shape == (18992, 2048)
    assert job.shapes["params"]["head"].shape == (2048, 18992)
    assert (job.batch, job.seq_len) == (1, 4096)  # 2 x 4096 positions a step


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
    cell, job = toy_job("toy-sdar.warm_start")
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
    assert job.tokens(3).shape == (2, 16)
    assert int(np.max(job.tokens(3))) < job.cfg.mask_token_id  # never the mask id
    # the step's noise: seed and step alone
    assert jax.random.key_data(job.step_key(3)).tolist() == (
        jax.random.key_data(job.step_key(3)).tolist()
    )
    assert jax.random.key_data(job.step_key(3)).tolist() != (
        jax.random.key_data(job.step_key(4)).tolist()
    )
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
    _, loss_later = job.train_step(job.init_state(), 1)  # other tokens, other noise
    assert loss_later != loss
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
        ({"hidden_act": "gelu"}, "SwiGLU"),
        ({"use_sliding_window": True}, "no sliding window"),
        ({"mlp_only_layers": [0]}, "sparse feed-forward in every layer"),
        ({"num_experts": 5}, "one id each"),
        ({"optimizer": {"name": "sgd"}}, "AdamW only"),
    ],
)
def test_a_configuration_the_model_cannot_run_is_refused_aloud(change, match):
    cell = manifest.resolve_cell(toy_manifest(), "toy-sdar.warm_start")
    with pytest.raises(ValueError, match=match):
        manifest.load_module(cell.job_path).make_job(
            dict(cell.config, **change), jax.devices()[:1], 1
        )


def test_a_model_the_tree_does_not_have_fails_at_once():
    """What a parent commit does under this PR's benchmark files: the
    job's import of the model module fails before anything is built."""
    cell = manifest.resolve_cell(toy_manifest(), "toy-sdar.warm_start")
    with pytest.raises(ImportError, match="no_such_model"):
        manifest.load_module(cell.job_path).make_job(
            dict(cell.config, model="no_such_model"), jax.devices()[:1], 1
        )


# --------------------------------------------------- the loops, toy size


@pytest.mark.parametrize("name", sorted(TOY_CELLS))
def test_every_loop_kind_runs_end_to_end_under_the_job(name, dirs):
    line = run_toy(name, dirs)
    assert line["correct"] is True, line
    cell = manifest.resolve_cell(toy_manifest(), name)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["compiles_in_window"] == 0
    assert all(c == {"value": 0, "limit": 0} for c in line["compared"].values())
    assert os.listdir(dirs["roots_parent"]) == []
    info = line["info"]
    if name == "toy-sdar.warm_start":
        assert set(line["compared"]) == {
            "leaves_differing", "steps_wrongly_resolved", "operations_failed",
        }
        cycles = info["cycles"]
        assert cycles == line["attempted"] >= 1
        # the weights alone: 50 of the state's 101 leaves, 6 B a parameter
        assert (info["leaves_restored"], info["leaves_of_the_state"]) == (50, 101)
        _, job = toy_job(name)
        n_params = (job.state_bytes - 4) // 14
        assert info["restored_bytes"] == [6 * n_params] * cycles
        # and the program's report of every cycle says the same
        assert info["leaves_selected"] == [50] * cycles
        assert info["bytes_selected"] == [6 * n_params] * cycles
        assert info["leaves_in_snapshot"] == [102] * cycles  # + the progress
        assert info["template_released_bytes"] == [0] * cycles  # roomy here
        assert info["first_losses_equal"] is True
        for key in ("fresh_state_s", "restore_s", "first_step_s"):
            assert len(info[key]) == cycles and min(info[key]) > 0
    if name == "toy-mixed.warm_start":
        assert info["leaves_restored"] == info["leaves_of_the_state"] == 28
        assert info["leaves_selected"] == [28] * info["cycles"]
    if name == "toy-mixed.warm_start_with_clock":
        assert info["leaves_selected"] == [29] * info["cycles"]


def test_the_first_step_of_the_new_stage_is_the_one_fresh_moments_give(dirs):
    """A cycle's loss is what step 0 gives on the saved weights under an
    optimizer at zero, and not what resuming the old optimizer gives: the
    restored weights are the saved ones (bit for bit, by the sums) and
    the loss a function of them, the step's tokens and the step's noise."""
    cell, job = toy_job("toy-sdar.warm_start", seed=5)
    state = job.init_state()
    for step in range(cell.traffic["warm_steps"]):
        state, _ = job.train_step(state, step)
    fresh = job.template()
    resumed, _ = job.train_step(jax.tree.map(jnp.copy, state), 0)
    warm = {"params": state["params"], "master": state["master"], "opt": fresh["opt"]}
    warm_started, want = job.train_step(warm, 0)
    line = run_toy("toy-sdar.warm_start", dirs, seed=5)
    assert line["correct"] is True and line["info"]["first_losses_equal"]
    assert line["info"]["first_loss"] == want
    # the two starts differ where the optimizer enters: the master after the step
    assert any(
        bool(jnp.any(a != b)) for a, b in zip(
            jax.tree.leaves(resumed["master"]), jax.tree.leaves(warm_started["master"])
        )
    )


# ------------------------------------------ faults, through the new loop


_patched = faults._patched


@contextlib.contextmanager
def flipped_byte_after_a_synchronous_save(under):
    """``faults.corrupt_newest_object`` flips its byte when an async
    save's ``wait`` returns; this loop's set-up saves synchronously, so
    the same flip is planted behind ``CheckpointManager.save``: one byte
    in the middle of the largest object whose path holds ``under``."""
    real = CheckpointManager.save

    def save(self, step, app_state, **kw):
        out = real(self, step, app_state, **kw)
        files = [
            os.path.join(d, f)
            for d, _, names in os.walk(os.path.join(self.base_path, f"step-{step}"))
            for f in names
            if not f.startswith(".") and under in os.path.join(d, f)
        ]
        victim = max(files, key=os.path.getsize)
        with open(victim, "r+b") as f:
            f.seek(os.path.getsize(victim) // 2)
            byte = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([byte[0] ^ 0x40]))
        return out

    with _patched(CheckpointManager, "save", save):
        yield


@contextlib.contextmanager
def restore_also_writes_an_optimizer_leaf(written=None):
    """A restore that touches what it was not given: after the real
    restore of the Statefuls named, one leaf of another Stateful with
    arrays (the optimizer: a moment) comes back as noise."""
    targets = []
    real_init, real_restore = harness.Run.__init__, CheckpointManager.restore

    def init(self, *args, **kw):
        real_init(self, *args, **kw)
        real_app_state = self.job.app_state

        def app_state(tree, step):
            out = real_app_state(tree, step)
            targets.append(out)
            return out

        self.job.app_state = app_state

    def restore(self, app_state, step=None, paths=None):
        got = real_restore(self, app_state, step=step, paths=paths)
        others = [
            s for key, s in targets[-1].items()
            if key not in app_state and faults._holds_arrays(s)
        ]
        leaves, treedef = jax.tree.flatten(others[0].state_dict())
        noise = lambda x: jax.random.normal(jax.random.key(0), x.shape, x.dtype)
        leaves[0] = (written or noise)(leaves[0])
        others[0].load_state_dict(jax.tree.unflatten(treedef, leaves))
        return got

    with _patched(harness.Run, "__init__", init), _patched(
        CheckpointManager, "restore", restore
    ):
        yield


@contextlib.contextmanager
def restore_also_writes_ones():
    """The same with float32 ones: 2048 equal words whose low bits are
    zero sum to 0 mod 2**32 in both of the reference's sums, so the sums
    of zeros pass them; the loop's own look for set bits does not."""
    with restore_also_writes_an_optimizer_leaf(jnp.ones_like):
        yield


FAULTS = {
    "lossy_save": faults.lossy_save,
    "restore_lands_nothing": faults.restore_lands_nothing,
    "restore_lands_half": faults.restore_lands_half,
    "flipped_byte_in_a_model_object": lambda: flipped_byte_after_a_synchronous_save(
        "/model/"
    ),
    "restore_also_writes_an_optimizer_leaf": restore_also_writes_an_optimizer_leaf,
    "restore_also_writes_ones": restore_also_writes_ones,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_turns_correct_false_through_the_new_loop(fault, dirs, capsys):
    name = "toy-sdar.warm_start"
    with FAULTS[fault]():
        line = run_toy(name, dirs)
    assert line["correct"] is False, line
    said = [
        json.loads(ln.split("MISMATCH ", 1)[1])
        for ln in capsys.readouterr().out.splitlines()
        if "MISMATCH" in ln
    ]
    assert said and said[0]["cell"] == name and said[0]["seed"] == 5
    differing = line["compared"].get("leaves_differing", {}).get("value")
    cycles = line["attempted"]
    if fault == "flipped_byte_in_a_model_object":
        # the restore's own checksum refuses the object: the loop raises
        assert line["compared"]["operations_failed"]["value"] == 1
        assert "hecksum" in said[0]["error"] or "corrupt" in said[0]["error"].lower()
    elif fault.startswith("restore_also_writes"):
        # one leaf a cycle, and it is the optimizer's
        assert differing == cycles
        assert {d["leaf"] for d in said} == {"['opt'][0].mu['embed']"}
        if fault.endswith("ones"):  # the sums read zeros; the bits do not
            assert all("holds set bits" in d["comparison"] for d in said)
        else:
            assert all(d["pinned_at_save"] == [0, 0] for d in said)
    elif fault == "restore_lands_nothing":
        assert differing == 50 * cycles  # every leaf of the model, no other
        assert all(d["leaf"].startswith(("['params']", "['master']")) for d in said)
    elif fault == "restore_lands_half":
        assert differing == 25 * cycles
    else:  # lossy_save: the matrices of params and master, rounded
        _, job = toy_job(name)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            {"params": job.shapes["params"], "master": job.shapes["master"]}
        )
        matrices = {jax.tree_util.keystr(p) for p, s in flat if len(s.shape) >= 2}
        assert {d["leaf"] for d in said} == matrices and differing == len(matrices) * cycles
    assert any(f.startswith(f"diagnosis-{name}-5") for f in os.listdir(dirs["out_dir"]))
    assert os.listdir(dirs["roots_parent"]) == []


def test_a_flipped_byte_in_what_the_restore_does_not_read_changes_nothing(dirs):
    """The subset restore reads its own objects alone: a corrupt object
    of the optimizer, which no cycle selects, is never opened."""
    with flipped_byte_after_a_synchronous_save("/optimizer/"):
        line = run_toy("toy-sdar.warm_start", dirs)
    assert line["correct"] is True, line


# --------------------------------------------------- the two new readers

READERS = ("restore_selected_h2d_share", "fresh_state_ms")


def _reader(name):
    return manifest.load_module(
        os.path.join(manifest.BENCH_DIR, "layers", name + ".py")
    ).read


def test_the_readers_on_recorded_observations_and_on_none():
    cycles = [
        {"fresh_state_s": 0.010, "restore_s": 4.0, "first_step_s": 0.5,
         "restored_bytes": 3_873_739_776},
        {"fresh_state_s": 0.014, "restore_s": 3.6, "first_step_s": 0.5,
         "restored_bytes": 3_873_739_776},
    ]
    obs = {"cycles": cycles, "probes": {"h2d_gbps": 11.6}, "state_bytes": 9_038_726_148}
    # 3.87 GB in a mean of 3.8 s = 1.0194 GB/s of 11.6
    assert _reader("restore_selected_h2d_share")(obs) == pytest.approx(
        100 * 3.873739776 / 3.8 / 11.6
    )
    assert _reader("fresh_state_ms")(obs) == pytest.approx(12.0)
    # ... where ``restore_h2d_share`` would read 2.33 times as much
    whole = _reader("restore_h2d_share")(obs)
    assert whole / _reader("restore_selected_h2d_share")(obs) == pytest.approx(
        9_038_726_148 / 3_873_739_776
    )
    # an untraced run (no probes), a loop that records neither (the
    # accepted kill_resume), a run of the save loop, nothing at all
    kill_resume = [{"restore_s": 4.0, "first_step_s": 0.1}]
    for nothing in (
        {"cycles": cycles},
        {"cycles": cycles, "probes": {}},
        {"cycles": kill_resume, "probes": {"h2d_gbps": 11.6}, "state_bytes": 8},
        {"saves": [{}], "probes": {"h2d_gbps": 11.6}},
        {"cycles": []},
        {},
    ):
        assert _reader("restore_selected_h2d_share")(nothing) is None, nothing
    for nothing in ({"cycles": kill_resume}, {"saves": [{}]}, {"cycles": []}, {}):
        assert _reader("fresh_state_ms")(nothing) is None, nothing


def test_a_traced_run_reads_both_through_a_manifest_that_lists_them(dirs):
    """The two readers' entries in ``BENCHMARK.json`` list the cell: the
    harness finds them by name and a traced run of the toy cell reports
    both beside the accepted metrics."""
    line = run_toy("toy-sdar.warm_start", dirs, trace=True)
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {
        "read_busy_share", "first_step_after_restore_ms",
        "restore_selected_h2d_share", "fresh_state_ms",
    }  # all but the device trace's: the CPU backend has no device plane
    assert all(v["value"] > 0 for v in line["metrics"].values())
