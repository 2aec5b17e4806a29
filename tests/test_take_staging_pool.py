"""The takes' pool of assembly buffers (staging_pool.py, "The take side").

A chunked leaf is assembled in a buffer leased from a process-wide pool
and the lease goes back when ``storage.write`` of the object has
returned: the second save of a process finds every buffer there, its
pages touched. Held here: the counters in the take's report, that a
buffer still leased (or still viewed by somebody's plug-in) is never
handed out, that every way out of a take gives its leases back and
leaves the snapshots that exist as they were, and that what stays
between saves is at most what one take leased.
"""

import asyncio
import gc
import json
import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import torchsnapshot_tpu.snapshot as snap_mod
from torchsnapshot_tpu import Snapshot, StateDict, staging_pool
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

CHUNK = 16 << 10
# Two leaves of one size and one of another are chunked; two are not.
SHAPES = {
    "w0": (4 * CHUNK // 4,),
    "w1": (4 * CHUNK // 4,),
    "emb": (3, 2 * CHUNK // 4),
    "bias": (16,),
    "count": (1,),
}
CHUNKED_BYTES = 4 * CHUNK + 4 * CHUNK + 6 * CHUNK


@pytest.fixture(autouse=True)
def _chunked_and_a_pool_of_its_own(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER", "1")
    monkeypatch.setenv("TPUSNAPSHOT_TRANSFER_CHUNK_BYTES", str(CHUNK))
    staging_pool.reset_take_staging_pool()
    yield
    staging_pool.reset_take_staging_pool()


def _state(seed):
    rng = np.random.default_rng(seed)
    return {
        name: jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for name, shape in SHAPES.items()
    }


def _pool_bytes(path):
    with open(os.path.join(path, ".report.json")) as f:
        block = next(s for s in json.load(f)["ranks"] if s)["stage_phases"]
    return block["pool_hit_bytes"], block["pool_miss_bytes"]


def _assert_restores(path, state):
    target = StateDict(**{k: jnp.zeros_like(v) for k, v in state.items()})
    Snapshot(path).restore({"m": target})
    for name, value in state.items():
        np.testing.assert_array_equal(
            np.asarray(target[name]).view(np.uint32),
            np.asarray(value).view(np.uint32),
            err_msg=f"{path}: {name}",
        )


def _stats():
    gc.collect()  # a lease that only a dead take held is parked, then released
    return staging_pool.get_take_staging_pool().stats()


def _use(monkeypatch, plugin_class):
    monkeypatch.setattr(
        snap_mod, "url_to_storage_plugin", lambda path: plugin_class(path)
    )


def _is_payload(io_req):
    return not io_req.path.startswith(".")


def _address(payload):
    return np.frombuffer(payload, dtype=np.uint8).ctypes.data


# ------------------------------------------------------------ the counters


def test_second_host_staged_take_finds_every_buffer_in_the_pool(tmp_path):
    first, second = _state(0), _state(1)
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    Snapshot.async_take(a, {"m": StateDict(**first)}, stage="host").wait()
    assert _pool_bytes(a) == (0, CHUNKED_BYTES)
    stats = _stats()
    assert stats["in_use_bytes"] == 0
    # The whole capture was leased at once, so all of it stays.
    assert stats["free_bytes"] == stats["capacity_bytes"] == CHUNKED_BYTES
    Snapshot.async_take(b, {"m": StateDict(**second)}, stage="host").wait()
    assert _pool_bytes(b) == (CHUNKED_BYTES, 0)
    assert _stats() == stats
    _assert_restores(a, first)
    _assert_restores(b, second)


@pytest.mark.parametrize("route", ["device_clones", "sync"])
def test_takes_that_stage_beside_their_writes_retain_their_high_water(
    tmp_path, route
):
    """Where staging runs beside the writes (the drain after device
    clones, a sync take under its budget) a buffer may be back before
    the next leaf leases: what stays is at most what was leased at
    once."""
    states = [_state(2), _state(3)]
    paths = [str(tmp_path / name) for name in "ab"]
    for path, state in zip(paths, states):
        if route == "sync":
            Snapshot.take(path, {"m": StateDict(**state)})
        else:
            Snapshot.async_take(path, {"m": StateDict(**state)}).wait()
        stats = _stats()
        assert stats["in_use_bytes"] == 0
        assert 0 < stats["free_bytes"] <= stats["capacity_bytes"] <= CHUNKED_BYTES
    for path, state in zip(paths, states):
        assert sum(_pool_bytes(path)) == CHUNKED_BYTES
        _assert_restores(path, state)


def test_a_stager_outside_a_take_leases_nothing():
    state = _state(2)
    # No take, no pool: nothing would bound what is retained.
    from torchsnapshot_tpu.io_preparer import ArrayBufferStager

    before = _stats()
    stager = ArrayBufferStager(state["w0"], eager_host_copy=False)
    payload = asyncio.run(stager.stage_buffer())
    assert bytes(payload) == np.asarray(state["w0"]).tobytes()
    assert stager._lease is None and _stats() == before


def test_trim_gives_the_memory_back(tmp_path):
    state = {"m": StateDict(**_state(3))}
    Snapshot.async_take(str(tmp_path / "a"), state, stage="host").wait()
    assert staging_pool.trim_take_staging_pool() == CHUNKED_BYTES
    assert _stats()["free_bytes"] == 0
    Snapshot.async_take(str(tmp_path / "b"), state, stage="host").wait()
    assert _pool_bytes(str(tmp_path / "b")) == (0, CHUNKED_BYTES)


# ---------------------------------------- never before the write returned


def test_pool_hands_out_only_what_is_released_and_unviewed():
    pool = staging_pool.get_take_staging_pool()
    pool.retain_up_to(3 * 4096)
    held = pool.acquire(4096)
    other = pool.acquire(4096)
    assert not held.reused and not other.reused
    assert other.buffer is not held.buffer
    view = held.as_array(np.dtype(np.uint8), [4096])
    view[:] = 7
    held.release()
    # Released, but somebody still reads it: a miss, and the buffer is
    # the viewer's from now on.
    again = pool.acquire(4096)
    assert not again.reused
    again.as_array(np.dtype(np.uint8), [4096])[:] = 9
    assert view.min() == view.max() == 7
    again.release()
    other.release()
    assert pool.stats()["free_bytes"] == 2 * 4096
    del view
    assert pool.acquire(4096).reused


def test_take_during_a_slow_drain_gets_no_leased_buffer(tmp_path, monkeypatch):
    gate = threading.Event()
    addresses = {"a": set(), "b": set()}

    class _SlowFS(FSStoragePlugin):
        async def write(self, io_req):
            name = os.path.basename(self.root)
            if _is_payload(io_req):
                while not gate.is_set():
                    await asyncio.sleep(0.005)
                addresses[name].add(_address(io_req.data))
            await super().write(io_req)

    _use(monkeypatch, _SlowFS)
    first, second = _state(4), _state(5)
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    pending_a = Snapshot.async_take(a, {"m": StateDict(**first)}, stage="host")
    # The first take's payloads wait in the plug-in, leased.
    assert _stats()["in_use_bytes"] == CHUNKED_BYTES
    pending_b = Snapshot.async_take(b, {"m": StateDict(**second)}, stage="host")
    assert _stats()["in_use_bytes"] == 2 * CHUNKED_BYTES
    gate.set()
    pending_a.wait()
    pending_b.wait()
    assert _pool_bytes(b) == (0, CHUNKED_BYTES)
    assert len(addresses["a"]) == len(addresses["b"]) == len(SHAPES)
    assert not addresses["a"] & addresses["b"]
    _assert_restores(a, first)
    _assert_restores(b, second)
    # Two captures were leased at once; one capture's bytes stay.
    stats = _stats()
    assert stats["in_use_bytes"] == 0
    assert stats["free_bytes"] <= stats["capacity_bytes"] == CHUNKED_BYTES


# ------------------------------------------------- every way out of a take


@pytest.mark.parametrize("how", ["write_raises", "cancelled", "plugin_retains"])
def test_no_lease_stays_out_and_no_snapshot_changes(tmp_path, monkeypatch, how):
    fail = threading.Event()
    kept = []  # (payload as the plug-in kept it, its bytes when written)

    class _FS(FSStoragePlugin):
        async def write(self, io_req):
            if fail.is_set() and _is_payload(io_req) and "w1" in io_req.path:
                if how == "cancelled":
                    raise KeyboardInterrupt()
                raise PermissionError("read-only file system")
            await super().write(io_req)
            if how == "plugin_retains" and _is_payload(io_req):
                kept.append((io_req.data, bytes(io_req.data)))

    _use(monkeypatch, _FS)
    monkeypatch.setenv("TPUSNAPSHOT_STORAGE_RETRIES", "0")
    states = [_state(6), _state(7), _state(8)]
    paths = [str(tmp_path / name) for name in "abc"]
    Snapshot.async_take(paths[0], {"m": StateDict(**states[0])}, stage="host").wait()
    assert _stats()["in_use_bytes"] == 0
    if how != "plugin_retains":
        fail.set()
        pending = Snapshot.async_take(
            paths[1], {"m": StateDict(**states[1])}, stage="host"
        )
        with pytest.raises(
            KeyboardInterrupt if how == "cancelled" else PermissionError
        ):
            pending.wait()
        fail.clear()
        assert not os.path.exists(os.path.join(paths[1], ".snapshot_metadata"))
        assert _stats()["in_use_bytes"] == 0
    Snapshot.async_take(paths[2], {"m": StateDict(**states[2])}, stage="host").wait()
    stats = _stats()
    assert stats["in_use_bytes"] == 0
    assert stats["free_bytes"] <= stats["capacity_bytes"] == CHUNKED_BYTES
    hit, miss = _pool_bytes(paths[2])
    assert hit + miss == CHUNKED_BYTES
    if how == "plugin_retains":
        # What the plug-in kept of the first take is the first take's
        # still: the third assembled elsewhere.
        assert hit == 0
        assert len(kept) == 2 * len(SHAPES)
        for payload, written in kept:
            assert bytes(payload) == written
    _assert_restores(paths[0], states[0])
    _assert_restores(paths[2], states[2])


def test_a_changed_tree_displaces_the_sizes_no_save_asks_for():
    """What is retained is bounded, not a take in flight: a miss evicts
    nothing, and a returning buffer that does not fit displaces the
    sizes unused for longest."""
    pool = staging_pool.get_take_staging_pool()
    pool.retain_up_to(3 * 4096)
    old = [pool.acquire(4096) for _ in range(3)]
    for lease in old:
        lease.release()
    assert pool.stats()["free_bytes"] == 3 * 4096
    new = [pool.acquire(6144) for _ in range(2)]
    assert pool.stats()["free_bytes"] == 3 * 4096  # the misses evicted nothing
    for lease in new:
        lease.release()
    # 2 x 6144 fit beside no 4096: all three went, oldest first.
    stats = pool.stats()
    assert stats["free_bytes"] == 2 * 6144 <= stats["capacity_bytes"]
    assert all(lease.reused for lease in (pool.acquire(6144), pool.acquire(6144)))
    assert not pool.acquire(4096).reused
