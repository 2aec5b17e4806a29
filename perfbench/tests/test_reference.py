"""The sums that decide ``correct``: the device version against the
numpy one, and what they have to notice."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import reference, roots
from perfbench.keys import seed_key
from torchsnapshot_tpu.parallel.mesh import make_mesh


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": rng.standard_normal((37, 24)).astype(np.float32),
        "b": [rng.standard_normal(5).astype(np.float32), np.ones((3, 4, 5), np.float32)],
        "h": rng.standard_normal((8, 16)).astype(jnp.bfloat16),
    }


def test_device_sums_equal_the_numpy_ones():
    t = tree()
    got = np.asarray(reference.make_checksum_fn()(jax.tree.map(jnp.asarray, t)))
    want = reference.checksums_numpy(jax.tree.leaves(t))
    np.testing.assert_array_equal(got, want)
    assert got.dtype == np.uint32 and got.shape == (4, 2)


def test_sharded_leaves_sum_to_the_same():
    mesh = make_mesh({"dp": 1, "tp": 4})
    x = np.random.default_rng(1).standard_normal((64, 8)).astype(np.float32)
    fn = reference.make_checksum_fn()
    plain = np.asarray(fn([jnp.asarray(x)]))
    for spec in (P("tp", None), P(None, "tp")):
        placed = jax.device_put(x, NamedSharding(mesh, spec))
        np.testing.assert_array_equal(np.asarray(fn([placed])), plain)


@pytest.mark.parametrize(
    "layout,spec,copies",
    [({"dp": 2, "tp": 2}, P(None, "tp"), 2), ({"dp": 2, "tp": 2}, P("tp", "dp"), 1),
     ({"dp": 2, "tp": 2}, P(), 4), ({"dp": 1, "tp": 4}, P("tp", None), 1)],
)
def test_every_copy_sums_to_the_whole_leaf(layout, spec, copies):
    """Each copy of a leaf, its blocks summed where they lie, gives the
    numpy sums of the whole leaf: a leaf on dp2 x tp2 sharded over tp
    alone has two copies, one replicated over the mesh four."""
    mesh = make_mesh(layout)
    x = np.random.default_rng(2).standard_normal((24, 8)).astype(np.float32)
    h = np.arange(6, dtype=np.int32)
    placed = [jax.device_put(x, NamedSharding(mesh, spec)), jnp.asarray(h)]
    got = reference.copy_sums(reference.make_copy_checksum_fn()(placed))
    want = reference.checksums_numpy([x, h])
    assert reference.differing_copies(reference.leaf_names([x, h]), want, got) == []
    assert sorted(r for leaf, r in got if leaf == 0) == list(range(copies))
    assert sorted(r for leaf, r in got if leaf == 1) == [0]


def test_a_second_copy_that_differs_is_named():
    """The blocks of the second copy (dp = 1) zeroed, the first left
    right: that copy, and it alone, differs, with the devices that hold
    it."""
    mesh = make_mesh({"dp": 2, "tp": 2})
    x = np.random.default_rng(3).standard_normal((16, 8)).astype(np.float32)
    placed = jax.device_put(x, NamedSharding(mesh, P(None, "tp")))
    blocks = [
        s.data if s.replica_id == 0 else jnp.zeros_like(s.data)
        for s in placed.addressable_shards
    ]
    broken = jax.make_array_from_single_device_arrays(x.shape, placed.sharding, blocks)
    got = reference.copy_sums(reference.make_copy_checksum_fn()([broken]))
    differing = reference.differing_copies(
        ["x"], reference.checksums_numpy([x]), got
    )
    second = sorted(s.device.id for s in placed.addressable_shards if s.replica_id == 1)
    assert [(d["leaf"], d["copy"], d["devices"]) for d in differing] == [("x", 1, second)]
    assert reference.differing_copies(["x", "y"], reference.checksums_numpy([x, x]), got)


@pytest.mark.parametrize("change", ["bit", "swap", "bf16"])
def test_sums_notice(change):
    t = tree()
    names = reference.leaf_names(t)
    before = reference.checksums_numpy(jax.tree.leaves(t))
    a = t["a"].copy()
    if change == "bit":
        a.view(np.uint32)[3, 7] ^= 1
    elif change == "swap":
        a[0, 0], a[5, 5] = a[5, 5], a[0, 0]
    else:
        a = a.astype(jnp.bfloat16).astype(np.float32)
    t["a"] = a
    after = reference.checksums_numpy(jax.tree.leaves(t))
    differing = reference.differing_leaves(names, before, after)
    assert [d["leaf"] for d in differing] == ["['a']"]


def test_a_tree_of_another_shape_differs():
    t = tree()
    sums = reference.checksums_numpy(jax.tree.leaves(t))
    assert reference.differing_leaves(reference.leaf_names(t), sums, sums[:-1])


def test_an_eight_byte_leaf_is_refused_aloud():
    """A job's state has leaves of 1, 2 or 4 bytes an item; the sums have
    no word for more, and say so where a silent cast would pass."""
    with pytest.raises(TypeError, match="no checksum for float64"):
        reference._leaf_sums(np.zeros(3, np.float64))
    sums = reference.checksums_numpy([np.arange(5, dtype=np.int8), np.int32(7)])
    assert sums.shape == (2, 2) and sums[1].tolist() == [7, 7]


def test_seeds_past_32_bits_give_keys_of_their_own():
    seeds = [0, 1, 2**31 - 1, 2**31, 2**31 + 5, 2**32 + 7]
    keys = {tuple(np.asarray(jax.random.key_data(seed_key(s))).tolist()) for s in seeds}
    assert len(keys) == len(seeds)


def test_a_run_sweeps_former_roots_and_removes_its_own(tmp_path):
    parent = tmp_path / "roots"
    (parent / "run-old" / "ckpt").mkdir(parents=True)
    (parent / "run-old" / "ckpt" / "x").write_text("left behind")
    (parent / "stray-file").write_text("x")
    ended = subprocess.Popen([sys.executable, "-c", "pass"])
    ended.wait()
    (parent / f"run-{ended.pid}-killed").mkdir()
    # A run side by side with this one: its root is not a former run's.
    alive = parent / f"run-{os.getppid()}-beside"
    alive.mkdir()
    with roots.run_root(str(parent)) as root:
        assert f"run-{os.getpid()}-" in root
        assert sorted(p.name for p in parent.iterdir()) == sorted(
            [root.split("/")[-1], alive.name]
        )
        (parent / root.split("/")[-1] / "payload").write_text("x")
    alive.rmdir()
    assert list(parent.iterdir()) == []
    with pytest.raises(RuntimeError):
        with roots.run_root(str(parent)):
            raise RuntimeError("a run that dies")
    assert list(parent.iterdir()) == []
