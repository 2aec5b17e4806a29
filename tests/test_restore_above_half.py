"""A restore whose target holds device arrays need not hold template and
landed arrays together (``snapshot._load_stateful``,
``io_preparer.template_crowds_device``, ``PytreeStateful.
release_template``), abstract targets, and the capture route of an async
take as a field of its report and a span."""

import json
import os
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import PytreeStateful, Snapshot, StateDict, io_preparer, tracing
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.models.nemotron_h import Moments


def _tree(seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return {
        "w": jax.random.normal(keys[0], (64, 32)),
        "half": jax.random.normal(keys[1], (16,)).astype(jnp.bfloat16),
        "opt": (Moments(jax.random.normal(keys[2], (64, 32)), jnp.ones((3,))), jnp.int32(7)),
    }


def _zeros(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def _equal(a, b):
    return all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _restore_report(path):
    with open(os.path.join(path, ".report.restore.json")) as f:
        return json.load(f)["ranks"][0]


@pytest.fixture
def saved(tmp_path):
    tree = _tree()
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"s": PytreeStateful(tree, convert=True)})
    return path, tree


@pytest.fixture
def crowded(monkeypatch):
    """A device that has room for next to nothing, by the library's own
    knob: whatever lands crowds its template."""
    monkeypatch.setenv("TPUSNAPSHOT_DEVICE_BUDGET_BYTES", "4096")


def test_cpu_devices_report_no_memory_so_nothing_is_released(saved):
    path, tree = saved
    target = PytreeStateful(_zeros(tree), convert=True)
    leaves = jax.tree.leaves(target.tree)
    assert io_preparer.template_crowds_device(leaves) is False
    Snapshot(path).restore({"s": target})
    assert _equal(target.tree, tree)
    assert _restore_report(path)["template_released_bytes"] == 0


@pytest.mark.parametrize("convert", [True, False])
def test_a_crowded_device_gets_its_template_released_before_the_reads(
    tmp_path, crowded, monkeypatch, convert
):
    tree = _tree() if convert else {"w": _tree()["w"], "v": [_tree()["half"]]}
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"s": PytreeStateful(tree, convert=convert)})
    target = PytreeStateful(_zeros(tree), convert=convert)
    refs = [weakref.ref(x) for x in jax.tree.leaves(target.tree)]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(target.tree))
    alive = []
    real = snapshot_mod.execute_read_reqs

    async def spying(*args, **kwargs):
        alive.append(sum(r() is not None for r in refs))
        return await real(*args, **kwargs)

    monkeypatch.setattr(snapshot_mod, "execute_read_reqs", spying)
    Snapshot(path).restore({"s": target})
    assert alive == [0]
    assert _equal(target.tree, tree)
    assert jax.tree.structure(target.tree) == jax.tree.structure(tree)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(target.tree))
    assert _restore_report(path)["template_released_bytes"] == nbytes


def test_an_array_the_caller_still_holds_outlives_the_release(saved, crowded):
    path, tree = saved
    zeros = _zeros(tree)
    kept = zeros["w"]
    Snapshot(path).restore({"s": PytreeStateful(zeros, convert=True)})
    assert not kept.is_deleted() and not np.asarray(kept).any()


def test_a_partial_restore_keeps_its_template(saved, crowded):
    path, tree = saved
    target = PytreeStateful(_zeros(tree), convert=True)
    Snapshot(path).restore({"s": target}, paths=["s/w"])
    assert _equal(target.tree["w"], tree["w"])
    assert isinstance(target.tree["half"], jax.Array) and not np.asarray(
        target.tree["half"].astype(jnp.float32)
    ).any()
    assert _restore_report(path)["template_released_bytes"] == 0


def test_a_stateful_that_cannot_release_is_restored_as_before(saved, crowded):
    path, tree = saved
    plain = {"w": tree["w"], "half": tree["half"]}
    Snapshot.take(path + "2", {"s": StateDict(**plain)})
    target = StateDict(**_zeros(plain))
    Snapshot(path + "2").restore({"s": target})
    assert _equal(dict(target), plain)
    assert _restore_report(path + "2")["template_released_bytes"] == 0


def test_a_failed_restore_leaves_shapes_and_a_retry_lands_on_them(
    saved, crowded, monkeypatch
):
    """Said aloud (the error propagates), the Stateful holds shapes with
    their shardings, and the same target restores on the next try."""
    path, tree = saved
    target = PytreeStateful(_zeros(tree), convert=True)
    real = snapshot_mod.execute_read_reqs

    async def failing(*args, **kwargs):
        raise OSError("the disk went away")

    monkeypatch.setattr(snapshot_mod, "execute_read_reqs", failing)
    with pytest.raises(OSError, match="the disk went away"):
        Snapshot(path).restore({"s": target})
    held = jax.tree.leaves(target.tree)
    assert all(isinstance(x, jax.ShapeDtypeStruct) and x.sharding is not None for x in held)
    assert jax.tree.structure(target.tree) == jax.tree.structure(tree)
    monkeypatch.setattr(snapshot_mod, "execute_read_reqs", real)
    Snapshot(path).restore({"s": target})
    assert _equal(target.tree, tree)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(target.tree))


def test_abstract_leaves_with_a_sharding_are_device_targets(saved):
    """What ``jax.eval_shape`` gives a caller that never made a template:
    the leaf lands where the sharding says, resharded if it says so."""
    path, tree = saved
    devices = jax.devices()
    mesh = jax.sharding.Mesh(np.array(devices[:2]), ("x",))
    split = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("x", None))
    one = jax.sharding.SingleDeviceSharding(devices[-1])
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree
    )
    abstract["w"] = jax.ShapeDtypeStruct((64, 32), jnp.float32, sharding=split)
    target = PytreeStateful(abstract, convert=True)
    Snapshot(path).restore({"s": target})
    assert _equal(target.tree, tree)
    assert target.tree["w"].sharding == split
    assert target.tree["half"].sharding.device_set == {devices[-1]}
    # without a sharding an abstract leaf says nothing about a device:
    # the leaf comes back on the host, as for any other template
    loose = PytreeStateful({"w": jax.ShapeDtypeStruct((64, 32), jnp.float32)})
    Snapshot.take(path + "w", {"s": PytreeStateful({"w": tree["w"]})})
    Snapshot(path + "w").restore({"s": loose})
    assert isinstance(loose.tree["w"], np.ndarray)
    with pytest.raises(RuntimeError, match="Shapes must match"):
        Snapshot(path + "w").restore(
            {"s": PytreeStateful({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=one)})}
        )


def test_release_template_keeps_structure_and_typed_keys():
    tree = {**_tree(), "key": jax.random.key(3)}
    stateful = PytreeStateful(tree, convert=True)
    stateful.release_template()
    assert jax.tree.structure(stateful.tree) == jax.tree.structure(tree)
    assert stateful.tree["key"] is tree["key"]  # a few bytes, restored through its data
    w = stateful.tree["w"]
    assert isinstance(w, jax.ShapeDtypeStruct) and w.sharding == tree["w"].sharding
    assert isinstance(stateful.tree["opt"][0], Moments)


@pytest.mark.parametrize(
    "route,stage,clones_fit",
    [
        ("device_clones", "auto", True),
        ("host_staging", "auto", False),
        ("host_staging", "host", True),
    ],
)
def test_the_take_report_names_the_capture_route(
    tmp_path, monkeypatch, caplog, route, stage, clones_fit
):
    """``capture_route`` and ``capture_host_staged_bytes`` in the take's
    report, a ``capture_host_stage`` span around the staging, and the
    fallback's warning in the words ``perfbench/harness.py`` counts."""
    if not clones_fit:
        monkeypatch.setattr(io_preparer, "device_clone", lambda arrays: None)
    tree = _tree()
    nbytes = sum(x.nbytes for x in jax.tree.leaves(tree))
    path = str(tmp_path / "snap")
    trace = str(tmp_path / "trace.json")
    tracing.enable(trace)
    try:
        with caplog.at_level("WARNING", logger="torchsnapshot_tpu"):
            Snapshot.async_take(
                path, {"s": PytreeStateful(tree, convert=True)}, stage=stage
            ).wait()
    finally:
        tracing.disable()
    with open(os.path.join(path, ".report.json")) as f:
        rank = json.load(f)["ranks"][0]
    assert rank["capture_route"] == route
    staged = rank["capture_host_staged_bytes"]
    assert staged == (nbytes if route == "host_staging" else 0)
    with open(trace) as f:
        spans = [e for e in json.load(f)["traceEvents"] if e["name"] == "capture_host_stage"]
    assert len(spans) == (2 if route == "host_staging" else 0)  # begin and end
    if spans:
        assert spans[0]["args"]["bytes"] == nbytes
    warned = sum("falling back to host staging" in r.getMessage() for r in caplog.records)
    assert warned == (0 if clones_fit else 1)
    restored = PytreeStateful(_zeros(tree), convert=True)
    Snapshot(path).restore({"s": restored})
    assert _equal(restored.tree, tree)
