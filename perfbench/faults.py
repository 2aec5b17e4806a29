"""Faults planted under the timed path, each of which has to turn
``correct`` false. The tests plant them at a toy size; ``control.py``
plants them on the chip at a cell's own size. No run of the benchmark
itself imports this file.

``lossy_save`` is the control: the guarantee "bit for bit" broken in the
way that would tempt a later PR, the state rounded to the nearest
precision below (float32 -> bfloat16) on its way to storage.
"""

import contextlib
import os

import jax
import jax.numpy as jnp

from torchsnapshot_tpu import CheckpointManager
from torchsnapshot_tpu.manager import PendingManagedSnapshot


@contextlib.contextmanager
def _patched(owner, name, replacement):
    original = getattr(owner, name)
    setattr(owner, name, replacement)
    try:
        yield original
    finally:
        setattr(owner, name, original)


_LOWER = {jnp.dtype("float32"): jnp.bfloat16, jnp.dtype("bfloat16"): jnp.float8_e4m3fn}


def _holds_arrays(stateful) -> bool:
    return any(isinstance(x, jax.Array) for x in jax.tree.leaves(stateful.state_dict()))


class _Rounded:
    """A Stateful's state with every float32 or bfloat16 matrix rounded
    one precision down, made once, when the save is called. Vectors,
    scalars and integer leaves pass as they are."""

    def __init__(self, stateful) -> None:
        self._state_dict = jax.tree.map(
            lambda x: x.astype(_LOWER[x.dtype]).astype(x.dtype)
            if isinstance(x, jax.Array) and x.ndim >= 2 and x.dtype in _LOWER
            else x,
            stateful.state_dict(),
        )

    def state_dict(self):
        return self._state_dict


def _rounded(app_state):
    return {
        key: _Rounded(stateful) if _holds_arrays(stateful) else stateful
        for key, stateful in app_state.items()
    }


@contextlib.contextmanager
def lossy_save():
    """Every save stores the matrices rounded one precision down."""
    real_async, real_sync = CheckpointManager.async_save, CheckpointManager.save

    def async_save(self, step, app_state, **kw):
        return real_async(self, step, _rounded(app_state), **kw)

    def save(self, step, app_state, **kw):
        return real_sync(self, step, _rounded(app_state), **kw)

    with _patched(CheckpointManager, "async_save", async_save), _patched(
        CheckpointManager, "save", save
    ):
        yield


@contextlib.contextmanager
def restore_lands_nothing():
    """``restore`` resolves the step, lands what holds no array (the
    progress) and leaves every Stateful with arrays as it was: a step
    that returns its state unchanged."""
    real = CheckpointManager.restore

    def restore(self, app_state, step=None, paths=None):
        rest = {k: s for k, s in app_state.items() if not _holds_arrays(s)}
        return real(self, rest, step=step, paths=paths)

    with _patched(CheckpointManager, "restore", restore):
        yield


@contextlib.contextmanager
def restore_lands_half():
    """``restore`` lands the first half of the leaves and leaves the
    rest of the target as it was."""
    real = CheckpointManager.restore

    def restore(self, app_state, step=None, paths=None):
        targets = [s for s in app_state.values() if _holds_arrays(s)]
        before = [jax.tree.leaves(s.state_dict()) for s in targets]
        got = real(self, app_state, step=step, paths=paths)
        for target, was in zip(targets, before):
            after, treedef = jax.tree.flatten(target.state_dict())
            half = len(after) // 2
            target.load_state_dict(
                jax.tree.unflatten(treedef, after[:half] + was[half:])
            )
        return got

    with _patched(CheckpointManager, "restore", restore):
        yield


@contextlib.contextmanager
def restore_swaps_shards():
    """Every leaf split over chips comes back with its shards one place
    on: the exchange between layouts done wrongly."""
    real = CheckpointManager.restore

    def restore(self, app_state, step=None, paths=None):
        got = real(self, app_state, step=step, paths=paths)

        def rolled(x):
            if not isinstance(x, jax.Array) or x.sharding.is_fully_replicated:
                return x
            shard_shape = x.sharding.shard_shape(x.shape)
            axis = next(i for i in range(x.ndim) if shard_shape[i] != x.shape[i])
            return jax.device_put(jnp.roll(x, shard_shape[axis], axis=axis), x.sharding)

        for target in app_state.values():
            target.load_state_dict(jax.tree.map(rolled, target.state_dict()))
        return got

    with _patched(CheckpointManager, "restore", restore):
        yield


@contextlib.contextmanager
def restore_lands_one_replica():
    """Every leaf held more than once comes back right in its first copy
    and zeroed in the others, as the template gave them: the exchange
    that lands a read block on every device holding it left out."""
    real = CheckpointManager.restore

    def restore(self, app_state, step=None, paths=None):
        got = real(self, app_state, step=step, paths=paths)

        def first_copy_only(x):
            if not isinstance(x, jax.Array):
                return x
            blocks = [
                s.data if s.replica_id == 0 else jnp.zeros_like(s.data)
                for s in x.addressable_shards
            ]
            return jax.make_array_from_single_device_arrays(x.shape, x.sharding, blocks)

        for target in app_state.values():
            if _holds_arrays(target):
                target.load_state_dict(jax.tree.map(first_copy_only, target.state_dict()))
        return got

    with _patched(CheckpointManager, "restore", restore):
        yield


@contextlib.contextmanager
def corrupt_newest_object():
    """Once a save is durable, one byte in the middle of its largest
    object is flipped on disk."""
    real = PendingManagedSnapshot.wait

    def wait(self, *args, **kw):
        snapshot = real(self, *args, **kw)
        step_dir = os.path.join(self._manager.base_path, f"step-{self._step}")
        files = [
            os.path.join(d, f)
            for d, _, names in os.walk(step_dir)
            for f in names
            if not f.startswith(".")
        ]
        if files:
            victim = max(files, key=os.path.getsize)
            with open(victim, "r+b") as f:
                f.seek(os.path.getsize(victim) // 2)
                byte = f.read(1)
                f.seek(-1, os.SEEK_CUR)
                f.write(bytes([byte[0] ^ 0x40]))
        return snapshot

    with _patched(PendingManagedSnapshot, "wait", wait):
        yield


FAULTS = {
    "lossy_save": lossy_save,
    "restore_lands_nothing": restore_lands_nothing,
    "restore_lands_half": restore_lands_half,
    "restore_swaps_shards": restore_swaps_shards,
    "restore_lands_one_replica": restore_lands_one_replica,
    "corrupt_newest_object": corrupt_newest_object,
}
