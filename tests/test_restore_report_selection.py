"""What a restore chose out of the snapshot, as three fields of its
report (``snapshot._load_stateful``, ``_finish_restore_report``):
``leaves_selected`` and ``bytes_selected`` (by the app state's keys or by
``paths=`` globs) beside ``leaves_in_snapshot``; and that a restore of a
subset writes nothing outside it."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import CheckpointManager, PytreeStateful, Snapshot, StateDict
from torchsnapshot_tpu.models.mixed_adamw import Moments


def _state(seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    params = {
        "w": jax.random.normal(keys[0], (64, 32)).astype(jnp.bfloat16),
        "norm": jax.random.normal(keys[1], (32,)).astype(jnp.bfloat16),
    }
    master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    moments = Moments(
        jax.tree.map(lambda m: m + 1, master), jax.tree.map(lambda m: m * m, master)
    )
    return {"params": params, "master": master, "opt": (moments, jnp.int32(7))}


def _app_state(state, step):
    return {
        "model": PytreeStateful({"params": state["params"], "master": state["master"]}),
        "optimizer": PytreeStateful(state["opt"], convert=True),
        "progress": StateDict(step=step),
    }


def _report(path):
    with open(os.path.join(path, ".report.restore.json")) as f:
        return json.load(f)["ranks"][0]


def _nbytes(tree):
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _bits(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


@pytest.fixture
def saved(tmp_path):
    state = _state()
    path = str(tmp_path / "snap")
    Snapshot.take(path, _app_state(state, 3))
    return path, state


# (what the restore is given, paths=) -> (leaves selected, of which keys)
SELECTIONS = {
    "everything": (("model", "optimizer", "progress"), None),
    "one_stateful_of_three": (("model",), None),
    "two_statefuls": (("model", "progress"), None),
    "paths_inside_one_stateful": (("model", "optimizer", "progress"), ["model/params/*"]),
    "paths_over_two_statefuls": (("model", "optimizer"), ["model/master/*", "optimizer/**"]),
}


@pytest.mark.parametrize("which", sorted(SELECTIONS))
def test_the_report_says_what_the_restore_chose(saved, which):
    path, state = saved
    keys, paths = SELECTIONS[which]
    fresh = jax.tree.map(jnp.zeros_like, state)
    target = _app_state(fresh, -1)
    Snapshot(path).restore({k: target[k] for k in keys}, paths=paths)
    report = _report(path)
    model = {"params": state["params"], "master": state["master"]}
    want_leaves, want_bytes = {
        "everything": (4 + 5 + 1, _nbytes(state)),
        "one_stateful_of_three": (4, _nbytes(model)),
        "two_statefuls": (4 + 1, _nbytes(model)),
        "paths_inside_one_stateful": (2, _nbytes(state["params"])),
        "paths_over_two_statefuls": (2 + 5, _nbytes(state["master"]) + _nbytes(state["opt"])),
    }[which]
    assert report["leaves_selected"] == want_leaves
    assert report["bytes_selected"] == want_bytes
    assert report["leaves_in_snapshot"] == 10  # whatever was chosen
    # what was chosen holds the saved bits; nothing else was written
    got = {
        "params": target["model"].tree["params"],
        "master": target["model"].tree["master"],
        "opt": target["optimizer"].tree,
        "step": target["progress"]["step"],
    }
    chosen = {
        "everything": {"params", "master", "opt", "step"},
        "one_stateful_of_three": {"params", "master"},
        "two_statefuls": {"params", "master", "step"},
        "paths_inside_one_stateful": {"params"},
        "paths_over_two_statefuls": {"master", "opt"},
    }[which]
    for part in ("params", "master", "opt"):
        want = state[part] if part in chosen else fresh[part]
        assert _bits(got[part]) == _bits(want), part
    assert got["step"] == (3 if "step" in chosen else -1)


def test_a_managers_restore_of_one_stateful_reports_the_same(tmp_path):
    """The call the warm-start cell makes: ``CheckpointManager.restore``
    given ``{"model": ...}`` alone of a full training snapshot."""
    state = _state(1)
    base = str(tmp_path / "ckpt")
    CheckpointManager(base).save(5, _app_state(state, 5))
    fresh = jax.tree.map(jnp.zeros_like, state)
    target = _app_state(fresh, -1)
    assert CheckpointManager(base).restore({"model": target["model"]}) == 5
    report = _report(os.path.join(base, "step-5"))
    assert report["leaves_selected"] == 4 and report["leaves_in_snapshot"] == 10
    assert report["bytes_selected"] == 6 * (64 * 32 + 32)
    assert _bits(target["model"].tree["master"]) == _bits(state["master"])
    assert not any(np.asarray(x).any() for x in jax.tree.leaves(target["optimizer"].tree))
    assert target["progress"]["step"] == -1
