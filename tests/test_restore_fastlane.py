"""Streaming zero-copy restore fast path (fastlane).

Covers the staging-buffer pool (reuse, capacity waits, the once-only
scheduler budget re-credit), the H2D overlap engine (transfers off the
consume wall, error surfacing before publication), chunk-granular
early region dispatch, concurrent restores sharing the pool without
profile cross-attribution, and the faultline crash-mid-stream
guarantee: a crash after some chunks are on device but before finalize
never publishes a torn leaf, and the retry is bit-exact.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchsnapshot_tpu import Snapshot, faultline as fl, staging_pool
from torchsnapshot_tpu.ops.transfer import H2DPipeline
from torchsnapshot_tpu.telemetry import consume_profile as _cprof


class _Holder:
    def __init__(self, sd):
        self.sd = sd

    def state_dict(self):
        return self.sd

    def load_state_dict(self, sd):
        self.sd = sd


@pytest.fixture(autouse=True)
def _fresh_pool(monkeypatch):
    staging_pool.reset_staging_pool()
    yield
    staging_pool.reset_staging_pool()


def _arr(nbytes, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(nbytes // 4), jnp.float32)


def _restore_report(root):
    import json
    import os

    with open(os.path.join(root, ".report.restore.json")) as f:
        return json.load(f)


# ------------------------------------------------------------ staging pool


def test_pool_reuses_exact_size_buffers():
    pool = staging_pool.StagingPool(capacity_bytes=1 << 20)
    a = pool.acquire(4096)
    backing = a.buffer
    a.release()
    b = pool.acquire(4096)
    assert b.buffer is backing  # exact-size reuse, zero allocation
    assert pool.stats()["in_use_bytes"] == 4096
    b.release()
    assert pool.stats()["in_use_bytes"] == 0
    assert pool.stats()["free_bytes"] == 4096


def test_pool_budget_recredit_fires_exactly_once():
    """The fastlane accounting fix: however many sub-reads shared a
    pooled buffer (and however many paths race to release it), the
    scheduler's host budget is re-credited once."""
    pool = staging_pool.StagingPool(capacity_bytes=1 << 20)
    credits = []
    lease = pool.acquire(8192)
    lease.set_budget_release(credits.append, 8192)
    lease.release()
    lease.release()  # double release: idempotent
    assert credits == [8192]
    # Releaser attached AFTER release (scheduler dispatch racing the
    # pipeline): fires immediately, still exactly once.
    lease2 = pool.acquire(8192)
    lease2.release()
    late = []
    lease2.set_budget_release(late.append, 8192)
    assert late == [8192]


def test_pool_disabled_by_env(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES", "0")
    staging_pool.reset_staging_pool()
    assert staging_pool.get_staging_pool() is None


def test_pool_capacity_wait_notes_pool_wait_and_never_deadlocks():
    pool = staging_pool.StagingPool(capacity_bytes=4096, max_wait_s=0.2)
    profile = _cprof.PhaseProfile()
    first = pool.acquire(4096)
    # Release from another thread while the second acquire waits. Pool
    # acquisitions happen inside consumer executor bodies, i.e. inside
    # a consume section — pool_wait is an in-consume sub-step.
    t = threading.Timer(0.05, first.release)
    t.start()
    with _cprof.consume_section():
        second = pool.acquire(4096, profile)
    t.join()
    assert second.buffer is first.buffer
    waited = profile.summary().get("pool_wait")
    assert waited and waited["seconds"] > 0
    # At capacity with NO release coming: the bounded wait expires and
    # the pool allocates past the cap rather than deadlocking.
    third = pool.acquire(4096, profile)
    assert third.buffer is not second.buffer
    second.release()
    third.release()


def test_pool_retains_free_bytes_only_up_to_capacity():
    pool = staging_pool.StagingPool(capacity_bytes=8192, max_wait_s=0.05)
    leases = [pool.acquire(4096) for _ in range(3)]  # 3rd overflows cap
    for lease in leases:
        lease.release()
    assert pool.stats()["free_bytes"] <= 8192


def test_split_state_budget_recredit_once_through_pool(monkeypatch):
    """_SplitObjectReadState over a pooled assembly buffer: N sub-reads
    share one buffer; the deferred-cost releaser fires once, at pool
    return — not per sub-read (the pre-fastlane single-use
    assumption)."""
    import asyncio

    from torchsnapshot_tpu.io_preparer import _SplitObjectReadState
    from torchsnapshot_tpu.io_types import BufferConsumer

    monkeypatch.setenv(
        "TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES", str(1 << 20)
    )
    staging_pool.reset_staging_pool()
    assert staging_pool.get_staging_pool() is not None

    sink = {}

    class _Consumer(BufferConsumer):
        async def consume_buffer(self, buf, executor=None):
            sink["payload"] = bytes(buf)

        def get_consuming_cost_bytes(self):
            return 10

    state = _SplitObjectReadState(10, _Consumer())
    reqs = state.add_sub_reads("p", 4)
    consumers = [r.buffer_consumer for r in reqs]
    credits = []
    consumers[0].set_cost_releaser(credits.append)

    async def _run():
        await consumers[0].consume_buffer(b"aaaa")
        await consumers[1].consume_buffer(b"bbbb")
        assert credits == []  # buffer still leased: reservation held
        await consumers[2].consume_buffer(b"cc")

    asyncio.run(_run())
    assert credits == [10]  # exactly once, at pool return
    assert sink["payload"] == b"aaaabbbbcc"
    # The buffer actually went back to the pool for reuse.
    assert staging_pool.get_staging_pool().stats()["free_bytes"] >= 10


# ------------------------------------------------- streaming + overlap engine


def test_pooled_state_stores_lease_before_anything_else(monkeypatch):
    """Regression (snapcheck SNAP006): ``_ensure_buf`` must make the
    lease reachable from the state BEFORE any other work — an exception
    between acquire and store orphaned the pooled buffer (and its
    exactly-once budget re-credit) with no owner left to release it."""
    from torchsnapshot_tpu import io_preparer as iop

    class _BoomLease:
        def __init__(self):
            self.released = False
            self._budget_cb = None
            self._budget_nbytes = 0
            self.credits = []

        @property
        def buffer(self):
            raise RuntimeError("boom between acquire and store")

        def release(self):
            self.released = True
            cb, self._budget_cb = self._budget_cb, None
            if cb is not None:
                cb(self._budget_nbytes)

        def set_budget_release(self, cb, nbytes):
            if self.released:
                cb(nbytes)
            else:
                self._budget_cb = cb
                self._budget_nbytes = nbytes

    class _FakePool:
        def __init__(self):
            self.lease = _BoomLease()

        def acquire(self, nbytes, profile=None):
            return self.lease

    pool = _FakePool()
    monkeypatch.setattr(staging_pool, "get_staging_pool", lambda: pool)
    state = iop._SplitObjectReadState.__new__(iop._SplitObjectReadState)
    iop._PooledAssemblyState.__init__(state, nbytes=64)
    credits = []
    state.set_cost_releaser(credits.append)
    with pytest.raises(RuntimeError, match="boom"):
        state._ensure_buf()
    # The lease is reachable, so the state's release path returns it —
    # AND the scheduler re-credit the lease never got attached to
    # still fires, exactly once.
    assert state._lease is pool.lease
    state._release_assembly_buffer()
    assert pool.lease.released
    assert credits == [64]
    state._release_assembly_buffer()  # idempotent: no double credit
    assert credits == [64]


def test_streaming_report_moves_h2d_off_the_consume_wall(
    tmp_path, monkeypatch
):
    """On the streaming path the H2D runs on the overlap engine: the
    flight report shows h2d_overlap carrying the payload bytes, no
    device_put inside consume, and the in-consume sub-steps still
    reconcile exactly against the consume wall."""
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(64 << 10))
    arr = _arr(1 << 20, seed=7)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder({"w": arr})})
    target = {"m": _Holder({"w": jnp.zeros_like(arr)})}
    Snapshot(path).restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.asarray(arr)
    )
    report = _restore_report(path)
    profile = next(
        s["consume_profile"]
        for s in report["ranks"]
        if s and s.get("consume_profile")
    )
    substeps = profile["substeps"]
    overlap = substeps.get("h2d_overlap")
    assert overlap and overlap["bytes"] == arr.nbytes
    assert substeps.get("device_put", {}).get("bytes", 0) == 0
    in_consume = sum(
        e["seconds"]
        for n, e in substeps.items()
        if n not in ("read_wait", "h2d_overlap", "overlap_other")
    )
    assert in_consume == pytest.approx(profile["consume_s"], abs=1e-3)
    assert profile.get("h2d_overlap_gbps", 0) > 0


def test_early_region_dispatch_for_compressed_leaf(tmp_path, monkeypatch):
    """A compressed leaf cannot stream raw ranges, but its region's H2D
    still dispatches on the overlap engine the moment its last copy
    lands (chunk-granular overlap), not at plan finalize."""
    monkeypatch.setenv("TPUSNAPSHOT_H2D_CHUNK_BYTES", "4096")
    arr = _arr(64 << 10, seed=3)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder({"w": arr})}, compression="zlib")

    submits = []
    orig_submit = H2DPipeline.submit

    def spy(self, host, device, profile=None):
        submits.append(int(getattr(host, "nbytes", len(host))))
        return orig_submit(self, host, device, profile=profile)

    monkeypatch.setattr(H2DPipeline, "submit", spy)
    target = {"m": _Holder({"w": jnp.zeros_like(arr)})}
    Snapshot(path).restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.asarray(arr)
    )
    assert submits == [arr.nbytes]
    report = _restore_report(path)
    profile = next(
        s["consume_profile"]
        for s in report["ranks"]
        if s and s.get("consume_profile")
    )
    assert profile["substeps"]["h2d_overlap"]["bytes"] == arr.nbytes


def test_engine_transfer_failure_surfaces_and_never_publishes(
    tmp_path, monkeypatch
):
    """A failed overlap-engine transfer must fail the restore (surfaced
    by the plan's finalize) with the template untouched — and a retry
    without the fault restores bit-exact."""
    from concurrent.futures import Future

    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(64 << 10))
    arr = _arr(512 << 10, seed=11)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder({"w": arr})})

    orig_submit = H2DPipeline.submit
    calls = [0]

    def failing(self, host, device, profile=None):
        calls[0] += 1
        if calls[0] == 3:
            fut = Future()
            fut.set_exception(RuntimeError("injected transfer failure"))
            return fut
        return orig_submit(self, host, device, profile=profile)

    monkeypatch.setattr(H2DPipeline, "submit", failing)
    target = {"m": _Holder({"w": jnp.zeros_like(arr)})}
    with pytest.raises(RuntimeError, match="injected transfer failure"):
        Snapshot(path).restore(target)
    # Torn-leaf guard: the template was never overwritten.
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.zeros(arr.shape, np.float32)
    )
    monkeypatch.setattr(H2DPipeline, "submit", orig_submit)
    target = {"m": _Holder({"w": jnp.zeros_like(arr)})}
    Snapshot(path).restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.asarray(arr)
    )


# ----------------------------------------------------- concurrency + faults


def test_concurrent_restores_share_pool_without_cross_attribution(
    tmp_path, monkeypatch
):
    """Two simultaneous restores draw from the ONE process pool; each
    flight report still reconciles exactly (sub-steps sum to its own
    consume wall — pooled buffers carry no cross-restore attribution)."""
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(64 << 10))
    monkeypatch.setenv(
        "TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES", str(8 << 20)
    )
    staging_pool.reset_staging_pool()
    roots, states = [], []
    for i in range(2):
        root = str(tmp_path / f"snap{i}")
        state = {"m": _Holder({"w": _arr(768 << 10, seed=20 + i)})}
        Snapshot.take(root, state)
        roots.append(root)
        states.append(state)
    errors = []

    def _restore(root, state):
        try:
            target = {
                "m": _Holder(
                    {"w": jnp.zeros_like(state["m"].sd["w"])}
                )
            }
            Snapshot(root).restore(target)
            np.testing.assert_array_equal(
                np.asarray(target["m"].sd["w"]),
                np.asarray(state["m"].sd["w"]),
            )
        except Exception as e:  # pragma: no cover
            errors.append(repr(e))

    threads = [
        threading.Thread(target=_restore, args=(r, s))
        for r, s in zip(roots, states)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    for root in roots:
        report = _restore_report(root)
        profile = next(
            s["consume_profile"]
            for s in report["ranks"]
            if s and s.get("consume_profile")
        )
        in_consume = sum(
            e["seconds"]
            for n, e in profile["substeps"].items()
            if n not in ("read_wait", "h2d_overlap", "overlap_other")
        )
        assert in_consume == pytest.approx(
            profile["consume_s"], abs=1e-3
        )
    pool = staging_pool.get_staging_pool()
    assert pool is not None
    # Every lease was donated back: nothing left in use.
    deadline = time.monotonic() + 10
    while (
        pool.stats()["in_use_bytes"] and time.monotonic() < deadline
    ):
        time.sleep(0.01)
    assert pool.stats()["in_use_bytes"] == 0


@pytest.mark.faultline
def test_crash_mid_stream_never_publishes_torn_leaf(tmp_path, monkeypatch):
    """A SimulatedCrash after some chunks are already device_put (but
    before finalize) fails the restore with the template untouched;
    the retry is bit-exact."""
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(64 << 10))
    arr = _arr(1 << 20, seed=5)  # 16 streamed sub-reads
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": _Holder({"w": arr})})

    target = {"m": _Holder({"w": jnp.zeros_like(arr)})}
    sched = fl.FaultSchedule().crash_on(op="read", path="0/m/w", nth=10)
    with fl.inject(sched):
        with pytest.raises(fl.SimulatedCrash):
            Snapshot(path).restore(target)
    # No torn leaf: the template still holds its zeros — nothing was
    # published from the partially-transferred stream.
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.zeros(arr.shape, np.float32)
    )
    # Retry without the fault: bit-exact.
    target = {"m": _Holder({"w": jnp.zeros_like(arr)})}
    Snapshot(path).restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.asarray(arr)
    )


def test_chunkstore_restore_pools_and_reconciles(tmp_path, monkeypatch):
    """Content-chunked (chunkstore) restores assemble through pooled
    buffers with decode+verify fused in the consume executors; the
    report still reconciles and the restore is bit-exact."""
    monkeypatch.setenv(
        "TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES", str(8 << 20)
    )
    staging_pool.reset_staging_pool()
    arr = _arr(256 << 10, seed=9)
    path = str(tmp_path / "snap")
    Snapshot.take(
        path, {"m": _Holder({"w": arr})}, chunks=True, codec="zlib"
    )
    target = {"m": _Holder({"w": jnp.zeros_like(arr)})}
    Snapshot(path).restore(target)
    np.testing.assert_array_equal(
        np.asarray(target["m"].sd["w"]), np.asarray(arr)
    )
    report = _restore_report(path)
    profile = next(
        s["consume_profile"]
        for s in report["ranks"]
        if s and s.get("consume_profile")
    )
    assert profile["substeps"]["decode"]["seconds"] > 0
    assert profile["substeps"]["verify"]["seconds"] > 0
    in_consume = sum(
        e["seconds"]
        for n, e in profile["substeps"].items()
        if n not in ("read_wait", "h2d_overlap", "overlap_other")
    )
    assert in_consume == pytest.approx(profile["consume_s"], abs=1e-3)


def test_depth_one_engine_never_deadlocks_finalize(tmp_path, monkeypatch):
    """TPUSNAPSHOT_H2D_DEPTH=1: an eager finalize fired from the
    engine's only worker must not block that worker on futures queued
    behind itself (finalize hops to its own pool). Two streamed leaves
    force queued transfers across plans."""
    from torchsnapshot_tpu.ops import transfer as transfer_mod

    monkeypatch.setenv("TPUSNAPSHOT_H2D_DEPTH", "1")
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(64 << 10))
    transfer_mod._reset_h2d_pipeline_for_tests()
    try:
        state = {
            "m": _Holder(
                {
                    "a": _arr(512 << 10, seed=31),
                    "b": _arr(512 << 10, seed=32),
                }
            )
        }
        path = str(tmp_path / "snap")
        Snapshot.take(path, state)
        target = {
            "m": _Holder(
                {
                    "a": jnp.zeros_like(state["m"].sd["a"]),
                    "b": jnp.zeros_like(state["m"].sd["b"]),
                }
            )
        }
        done = []

        def _run():
            Snapshot(path).restore(target)
            done.append(1)

        t = threading.Thread(target=_run)
        t.start()
        t.join(timeout=120)
        assert done == [1], "restore deadlocked at H2D depth 1"
        for k in ("a", "b"):
            np.testing.assert_array_equal(
                np.asarray(target["m"].sd[k]),
                np.asarray(state["m"].sd[k]),
            )
    finally:
        transfer_mod._reset_h2d_pipeline_for_tests()


def test_identity_chunk_decode_writes_straight_into_assembly(monkeypatch):
    """decode_and_verify_chunk's zero-copy hand-off: an identity chunk
    verifies on the stored view and lands in ``out`` with one copy;
    corruption still raises before anything is written back."""
    from torchsnapshot_tpu.chunkstore import decode_and_verify_chunk
    from torchsnapshot_tpu.fingerprint import fingerprint_host

    payload = np.arange(256, dtype=np.uint8).tobytes()
    key = f"{fingerprint_host(payload)}-{len(payload)}-raw"
    rec = {"k": key, "n": len(payload), "c": None}
    out = bytearray(len(payload))
    ret = decode_and_verify_chunk(
        rec, "uint8", payload, out=memoryview(out)
    )
    assert ret is None  # wrote in place
    assert bytes(out) == payload
    # Without out: the legacy contract returns the bytes.
    assert decode_and_verify_chunk(rec, "uint8", payload) == payload
    corrupt = bytearray(payload)
    corrupt[7] ^= 0xFF
    with pytest.raises(RuntimeError, match="fingerprint"):
        decode_and_verify_chunk(
            rec, "uint8", bytes(corrupt), out=memoryview(out)
        )
