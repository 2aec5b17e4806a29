"""Async snapshot tests (BASELINE.json north star: async take with
bounded step stall; SURVEY §7 step 8)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import PendingSnapshot, Snapshot, StateDict
from torchsnapshot_tpu.coord import DictStore, StoreCoordinator
from torchsnapshot_tpu.utils.test_utils import assert_state_dict_eq


class _Holder:
    def __init__(self, sd):
        self.sd = sd

    def state_dict(self):
        return self.sd

    def load_state_dict(self, sd):
        self.sd = sd


def test_async_take_round_trip(tmp_path):
    params = {"w": jnp.arange(1024, dtype=jnp.float32).reshape(32, 32)}
    pending = Snapshot.async_take(str(tmp_path / "snap"), {"m": _Holder(params)})
    assert isinstance(pending, PendingSnapshot)
    snap = pending.wait()
    assert pending.done()
    target = _Holder({"w": jnp.zeros((32, 32), dtype=jnp.float32)})
    snap.restore({"m": target})
    np.testing.assert_array_equal(np.asarray(target.sd["w"]), np.asarray(params["w"]))


def test_async_take_consistent_cut(tmp_path):
    """Mutating state after async_take returns must not affect the
    snapshot (staging = consistent cut)."""
    state = {"w": np.arange(100, dtype=np.float32)}
    holder = _Holder(state)
    pending = Snapshot.async_take(str(tmp_path / "snap"), {"m": holder})
    # Mutate immediately — before writes necessarily finished.
    state["w"][:] = -1.0
    snap = pending.wait()
    target = _Holder({"w": np.zeros(100, dtype=np.float32)})
    snap.restore({"m": target})
    np.testing.assert_array_equal(target.sd["w"], np.arange(100, dtype=np.float32))


def test_async_take_donation_safe(tmp_path):
    """Buffers may be donated (deleted) by the next jit step immediately
    after async_take returns; staging must already have happened."""
    import jax

    arr = jnp.arange(4096.0)
    pending = Snapshot.async_take(str(tmp_path / "snap"), {"m": _Holder({"w": arr})})
    arr.delete()  # simulate jit buffer donation
    snap = pending.wait()
    target = _Holder({"w": jnp.zeros(4096)})
    snap.restore({"m": target})
    assert float(np.asarray(target.sd["w"])[123]) == 123.0


def test_async_take_error_surfaces():
    class _Unpicklable:
        def __reduce__(self):
            raise RuntimeError("cannot pickle me")

    with pytest.raises(RuntimeError, match="cannot pickle me"):
        # Pickling happens at prepare time (synchronously).
        Snapshot.async_take("memory://async-err", {"m": _Holder({"o": _Unpicklable()})})


def test_async_take_multirank(tmp_path):
    path = str(tmp_path / "snap")

    def worker_take(coord, rank):
        pending = Snapshot.async_take(
            path, {"st": StateDict(v=rank)}, coord=coord
        )
        pending.wait()

    store = DictStore()
    errors = []

    def worker(rank):
        try:
            coord = StoreCoordinator(store, rank, 2, timeout_s=60)
            worker_take(coord, rank)
        except BaseException:  # pragma: no cover
            import traceback

            errors.append(traceback.format_exc())

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[0]

    def worker_restore(coord, rank):
        app = {"st": StateDict(v=-1)}
        Snapshot(path).restore(app, coord=coord)
        assert app["st"]["v"] == rank

    store2 = DictStore()
    threads = [
        threading.Thread(
            target=lambda r=r: worker_restore(StoreCoordinator(store2, r, 2, 60), r)
        )
        for r in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)


@pytest.mark.parametrize("stage", ["device", "host", "auto"])
def test_async_take_stage_modes(tmp_path, stage):
    """All staging modes produce identical, donation-safe snapshots."""
    arr = jnp.arange(2048, dtype=jnp.float32) * 3.0
    sharded = {"w": arr, "b": np.full(16, 7.0, dtype=np.float32)}
    pending = Snapshot.async_take(
        str(tmp_path / "snap"), {"m": _Holder(dict(sharded))}, stage=stage
    )
    arr.delete()  # simulate jit buffer donation
    sharded["b"][:] = -1.0  # mutate host memory after the cut
    snap = pending.wait()
    target = _Holder(
        {"w": jnp.zeros(2048), "b": np.zeros(16, dtype=np.float32)}
    )
    snap.restore({"m": target})
    np.testing.assert_array_equal(
        np.asarray(target.sd["w"]), np.arange(2048, dtype=np.float32) * 3.0
    )
    np.testing.assert_array_equal(target.sd["b"], np.full(16, 7.0))


def test_async_take_invalid_stage(tmp_path):
    with pytest.raises(ValueError, match="stage"):
        Snapshot.async_take(
            str(tmp_path / "snap"), {"m": _Holder({})}, stage="bogus"
        )


@pytest.mark.parametrize("stage", ["device", "host"])
def test_async_take_sharded_array(tmp_path, stage):
    """Device-staged async take of a partitioned array: clones preserve
    sharding; the snapshot survives deletion of the source (donation)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    arr = jax.device_put(
        jnp.arange(64.0).reshape(8, 8), NamedSharding(mesh, P("x", None))
    )
    pending = Snapshot.async_take(
        str(tmp_path / "snap"), {"m": _Holder({"w": arr})}, stage=stage
    )
    arr.delete()
    snap = pending.wait()

    # Elastic restore onto a smaller mesh.
    mesh2 = Mesh(np.array(jax.devices()[:2]), ("x",))
    template = jax.device_put(
        jnp.zeros((8, 8)), NamedSharding(mesh2, P(None, "x"))
    )
    target = _Holder({"w": template})
    snap.restore({"m": target})
    np.testing.assert_array_equal(
        np.asarray(target.sd["w"]), np.arange(64.0).reshape(8, 8)
    )


def test_async_take_background_write_failure_surfaces(tmp_path, monkeypatch):
    """A storage failure in the background drain must surface on wait(),
    and no metadata commit may appear (the snapshot stays invisible)."""
    import os
    import torchsnapshot_tpu.snapshot as snap_mod
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    class _FailingFS(FSStoragePlugin):
        async def write(self, io_req):
            if not io_req.path.startswith(".completed"):
                raise IOError("disk on fire")
            await super().write(io_req)

    monkeypatch.setattr(
        snap_mod, "url_to_storage_plugin", lambda path: _FailingFS(path)
    )
    pending = Snapshot.async_take(
        str(tmp_path / "snap"), {"m": _Holder({"w": jnp.arange(16.0)})}
    )
    with pytest.raises(IOError, match="disk on fire"):
        pending.wait()
    assert not os.path.exists(tmp_path / "snap" / ".snapshot_metadata")


def test_concurrent_async_takes_to_distinct_paths(tmp_path):
    """Two in-flight async snapshots (e.g. overlapping checkpoint
    cadences) must drain independently and both commit correctly."""
    a = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64)}
    b = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64) * 2}
    pa = Snapshot.async_take(str(tmp_path / "a"), {"m": _Holder(a)})
    pb = Snapshot.async_take(str(tmp_path / "b"), {"m": _Holder(b)})
    sa, sb = pa.wait(), pb.wait()

    ta = {"m": _Holder({"w": jnp.zeros((64, 64), jnp.float32)})}
    tb = {"m": _Holder({"w": jnp.zeros((64, 64), jnp.float32)})}
    sa.restore(ta)
    sb.restore(tb)
    np.testing.assert_array_equal(np.asarray(ta["m"].sd["w"]), np.asarray(a["w"]))
    np.testing.assert_array_equal(np.asarray(tb["m"].sd["w"]), np.asarray(b["w"]))


def test_many_small_leaves_round_trip(tmp_path):
    """2000-leaf state: manifest, scheduler, and storage must stay
    linear-ish (regression guard for per-leaf overhead blowups)."""
    leaves = {f"k{i:04d}": jnp.full((4, 4), i, jnp.float32) for i in range(2000)}
    state = StateDict(**leaves)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"s": state})
    target = StateDict(**{k: jnp.zeros((4, 4), jnp.float32) for k in leaves})
    Snapshot(path).restore({"s": target})
    assert float(target["k1999"][0, 0]) == 1999.0
    assert float(target["k0000"][0, 0]) == 0.0
    assert len(Snapshot(path).get_manifest()) >= 2000


def test_failed_take_leaves_no_commit_and_sweep_recovers(tmp_path):
    """Crash-recovery story: a take that dies mid-write must leave the
    path UNCOMMITTED (no metadata document -> restore raises not-found)
    with its partial writes stranded, a subsequent take to the same path
    must succeed, and delete(sweep=True) then leaves nothing behind
    (orphan-specific collection is covered by the delete-sweep tests)."""
    import os
    import threading

    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    path = str(tmp_path / "snap")
    state = StateDict(
        a=jnp.arange(64, dtype=jnp.float32),
        b=jnp.ones((32,), dtype=jnp.float32),
    )

    real_write = FSStoragePlugin._write_sync
    writes = []
    write_lock = threading.Lock()

    def dying_write(self, io_req):
        # Decide under a lock BEFORE writing: with 2-way write
        # concurrency both writers could otherwise observe len==2 and
        # raise, leaving zero partial writes to recover from. This way
        # write #1 always lands (asyncio.run joins the default executor
        # on teardown) and write #2 always dies.
        with write_lock:
            writes.append(io_req.path)
            n = len(writes)
        if n == 2:
            raise OSError("disk gone")
        real_write(self, io_req)

    FSStoragePlugin._write_sync = dying_write
    try:
        # Storage retries would mask the injected failure; disable.
        os.environ["TPUSNAPSHOT_STORAGE_RETRIES"] = "0"
        with pytest.raises(OSError, match="disk gone"):
            Snapshot.take(path, {"s": state})
    finally:
        FSStoragePlugin._write_sync = real_write
        os.environ.pop("TPUSNAPSHOT_STORAGE_RETRIES", None)

    # The crash stranded at least write #1's object, uncommitted:
    # metadata absent, restore refuses.
    stranded = [
        os.path.join(dp, f) for dp, _, fs in os.walk(path) for f in fs
    ]
    assert stranded, "the failed take should have landed a partial write"
    with pytest.raises(FileNotFoundError):
        Snapshot(path).restore({"s": StateDict(a=jnp.zeros(64), b=jnp.zeros(32))})

    # The same path takes cleanly afterwards (fresh take overwrites), and
    # the snapshot round-trips.
    Snapshot.take(path, {"s": state})
    target = StateDict(
        a=jnp.zeros(64, dtype=jnp.float32), b=jnp.zeros(32, dtype=jnp.float32)
    )
    Snapshot(path).restore({"s": target})
    np.testing.assert_array_equal(np.asarray(target["a"]), np.asarray(state["a"]))

    # Sweep-delete collects everything, including any orphan of the
    # failed attempt.
    Snapshot(path).delete(sweep=True)
    leftovers = [
        os.path.join(dp, f) for dp, _, fs in os.walk(path) for f in fs
    ]
    assert leftovers == []


def test_stale_async_commit_cannot_satisfy_new_take(tmp_path):
    """take_id nonces: a pending wait() for take B must not accept take
    A's already-committed metadata at the same path (the marker/metadata
    poll matches on the nonce, not mere existence). Take B's metadata
    commit is artificially delayed, so an existence-based poll WOULD
    return early — while only A's document exists — and the
    nonce-at-wait-return assertion below would catch it."""
    import os
    import threading
    import time as _time

    from torchsnapshot_tpu.manifest import SnapshotMetadata
    from torchsnapshot_tpu.snapshot import SNAPSHOT_METADATA_FNAME
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    path = str(tmp_path / "snap")
    a = StateDict(x=jnp.zeros(8))
    b = StateDict(x=jnp.ones(8))

    def read_meta():
        with open(os.path.join(path, SNAPSHOT_METADATA_FNAME)) as f:
            return SnapshotMetadata.from_yaml(f.read())

    Snapshot.async_take(path, {"s": a}).wait()
    meta_a = read_meta()

    real_write = FSStoragePlugin._write_sync
    delay_metadata = threading.Event()
    delay_metadata.set()

    def slow_metadata_write(self, io_req):
        if delay_metadata.is_set() and io_req.path == SNAPSHOT_METADATA_FNAME:
            _time.sleep(0.5)
        real_write(self, io_req)

    FSStoragePlugin._write_sync = slow_metadata_write
    try:
        pending_b = Snapshot.async_take(path, {"s": b})
        nonce_b = pending_b._background.take_id
        assert nonce_b and nonce_b != meta_a.take_id
        pending_b.wait()
        # At the instant wait() returns, the visible metadata must
        # already be B's — an existence-based poll would have returned
        # ~0.5 s earlier with A's document still in place.
        meta_at_return = read_meta()
        assert meta_at_return.take_id == nonce_b
    finally:
        FSStoragePlugin._write_sync = real_write

    target = StateDict(x=jnp.full((8,), 7.0))
    Snapshot(path).restore({"s": target})
    np.testing.assert_array_equal(np.asarray(target["x"]), np.ones(8))


def test_wait_timeout_bounds_hung_drain(tmp_path, monkeypatch):
    """wait(timeout_s) must bound the background-drain join (VERDICT r3
    weak #4): a hung storage backend surfaces as a prompt TimeoutError
    naming the stuck phase, and a later wait() can still succeed once
    the backend unblocks."""
    import torchsnapshot_tpu.snapshot as snap_mod
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    release = threading.Event()

    class _HangingFS(FSStoragePlugin):
        async def write(self, io_req):
            if not io_req.path.startswith((".completed", ".snapshot")):
                # Block the drain until the test releases it (simulated
                # wedged backend); poll so the event works from asyncio.
                import asyncio as _a

                while not release.is_set():
                    await _a.sleep(0.01)
            await super().write(io_req)

    monkeypatch.setattr(
        snap_mod, "url_to_storage_plugin", lambda path: _HangingFS(path)
    )
    pending = Snapshot.async_take(
        str(tmp_path / "snap"), {"m": _Holder(StateDict(w=jnp.arange(8.0)))}
    )
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="storage writes"):
        pending.wait(timeout_s=0.5)
    assert time.monotonic() - t0 < 10
    release.set()
    snap = pending.wait(timeout_s=60)
    target = {"m": _Holder(StateDict(w=jnp.zeros(8)))}
    snap.restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.arange(8.0)
    )


def test_wait_timeout_on_metadata_poll_is_retryable(tmp_path, monkeypatch):
    """A wait() that times out in the METADATA poll (drain finished,
    commit not yet observable — e.g. rank 0 still consolidating) must
    leave the storage plugin open so a later wait() can resume polling
    and succeed."""
    import torchsnapshot_tpu.snapshot as snap_mod
    from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

    release = threading.Event()
    closes = []

    class _HidingFS(FSStoragePlugin):
        async def read(self, io_req):
            if (
                io_req.path == ".snapshot_metadata"
                and not release.is_set()
            ):
                raise FileNotFoundError(io_req.path)
            await super().read(io_req)

        def close(self):
            closes.append(True)
            super().close()

    monkeypatch.setattr(
        snap_mod, "url_to_storage_plugin", lambda path: _HidingFS(path)
    )
    pending = Snapshot.async_take(
        str(tmp_path / "snap"), {"m": _Holder(StateDict(w=jnp.arange(4.0)))}
    )
    with pytest.raises(TimeoutError, match="metadata"):
        pending.wait(timeout_s=2)
    assert not closes  # storage stayed open for the retry
    release.set()
    snap = pending.wait(timeout_s=60)
    assert closes  # closed on success
    target = {"m": _Holder(StateDict(w=jnp.zeros(4)))}
    snap.restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.arange(4.0)
    )


def test_clone_oom_check_knob(tmp_path, monkeypatch):
    """TPUSNAPSHOT_CLONE_OOM_CHECK=0 removes the synchronous
    block_until_ready from the consistent-cut clone; the round trip
    stays bit-exact either way."""
    import torchsnapshot_tpu.ops.transfer as transfer_mod

    calls = []
    orig = jax.block_until_ready

    def counting(x):
        calls.append(1)
        return orig(x)

    monkeypatch.setattr(jax, "block_until_ready", counting)
    arrs = [jnp.arange(64.0), jnp.ones((8, 8))]

    clones = transfer_mod.device_clone(arrs)
    assert len(calls) == 1  # default: one batched OOM-check wait
    np.testing.assert_array_equal(np.asarray(clones[0]), np.arange(64.0))

    calls.clear()
    monkeypatch.setenv("TPUSNAPSHOT_CLONE_OOM_CHECK", "0")
    clones = transfer_mod.device_clone(arrs)
    assert calls == []  # no blocking wait on the stall path
    np.testing.assert_array_equal(np.asarray(clones[1]), np.ones((8, 8)))

    # Whole async take under the knob: still bit-exact.
    pending = Snapshot.async_take(
        str(tmp_path / "snap"),
        {"m": _Holder(StateDict(w=jnp.arange(32.0)))},
    )
    snap = pending.wait()
    target = {"m": _Holder(StateDict(w=jnp.zeros(32)))}
    snap.restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.arange(32.0)
    )


def test_async_timeout_names_all_missing_ranks(tmp_path):
    """_collect_completion_manifests' timeout error enumerates every
    straggler rank, not just the first missing one."""
    import asyncio

    from torchsnapshot_tpu.manifest import SnapshotMetadata
    from torchsnapshot_tpu.io_types import IOReq
    from torchsnapshot_tpu.snapshot import _collect_completion_manifests
    from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

    storage = MemoryStoragePlugin()
    nonce = "abc123"
    # Ranks 0 and 2 completed; 1 and 3 never did.
    for r in (0, 2):
        doc = SnapshotMetadata(
            version="v", world_size=4, manifest={}, take_id=nonce
        ).to_yaml()
        req = IOReq(path=f".completed/{nonce}/{r}")
        req.buf.write(doc.encode())
        asyncio.run(storage.write(req))

    with pytest.raises(TimeoutError, match=r"rank\(s\) \[1, 3\]"):
        asyncio.run(
            _collect_completion_manifests(storage, 4, nonce, timeout_s=0.3)
        )
