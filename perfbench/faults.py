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

from torchsnapshot_tpu import CheckpointManager, PytreeStateful
from torchsnapshot_tpu.manager import PendingManagedSnapshot


@contextlib.contextmanager
def _patched(owner, name, replacement):
    original = getattr(owner, name)
    setattr(owner, name, replacement)
    try:
        yield original
    finally:
        setattr(owner, name, original)


def _rounded(app_state):
    lower = {jnp.dtype("float32"): jnp.bfloat16, jnp.dtype("bfloat16"): jnp.float8_e4m3fn}
    out = dict(app_state)
    for key, stateful in app_state.items():
        if isinstance(stateful, PytreeStateful):
            out[key] = PytreeStateful(
                jax.tree.map(
                    lambda x: x.astype(lower[x.dtype]).astype(x.dtype)
                    if x.ndim >= 2
                    else x,
                    stateful.tree,
                )
            )
    return out


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
    """``restore`` resolves the step and leaves the target as it was: a
    step that returns its state unchanged."""

    def restore(self, app_state, step=None, paths=None):
        app_state["progress"]["step"] = self.latest_step() if step is None else step
        return app_state["progress"]["step"]

    with _patched(CheckpointManager, "restore", restore):
        yield


@contextlib.contextmanager
def restore_lands_half():
    """``restore`` lands the first half of the leaves and leaves the
    rest of the target as it was."""
    real = CheckpointManager.restore

    def restore(self, app_state, step=None, paths=None):
        target = app_state["train"]
        before = jax.tree.leaves(target.tree)
        got = real(self, app_state, step=step, paths=paths)
        after, treedef = jax.tree.flatten(target.tree)
        half = len(after) // 2
        target.tree = jax.tree.unflatten(treedef, after[:half] + before[half:])
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
        target = app_state["train"]

        def rolled(x):
            if x.sharding.is_fully_replicated:
                return x
            shard_shape = x.sharding.shard_shape(x.shape)
            axis = next(i for i in range(x.ndim) if shard_shape[i] != x.shape[i])
            return jax.device_put(jnp.roll(x, shard_shape[axis], axis=axis), x.sharding)

        target.tree = jax.tree.map(rolled, target.tree)
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
    "corrupt_newest_object": corrupt_newest_object,
}
