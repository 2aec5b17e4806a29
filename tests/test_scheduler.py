"""Scheduler pipeline tests (reference analog: scheduler.py behavior)."""

import asyncio
import time

import pytest

from torchsnapshot_tpu.coord import NoOpCoordinator
from torchsnapshot_tpu.io_types import (
    BufferConsumer,
    BufferStager,
    IOReq,
    ReadReq,
    StoragePlugin,
    WriteReq,
)
from torchsnapshot_tpu.scheduler import (
    execute_read_reqs,
    execute_write_reqs,
    get_local_world_size,
    get_process_memory_budget_bytes,
)
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin


class _Stager(BufferStager):
    def __init__(self, payload: bytes, tracker=None):
        self.payload = payload
        self.tracker = tracker

    async def stage_buffer(self, executor=None):
        if self.tracker is not None:
            self.tracker["staging"] += 1
            self.tracker["max_staging"] = max(
                self.tracker["max_staging"], self.tracker["staging"]
            )
            await asyncio.sleep(0.005)
            self.tracker["staging"] -= 1
        return self.payload

    def get_staging_cost_bytes(self) -> int:
        return len(self.payload)


class _Consumer(BufferConsumer):
    def __init__(self, sink, key):
        self.sink = sink
        self.key = key

    async def consume_buffer(self, buf, executor=None):
        self.sink[self.key] = bytes(buf)

    def get_consuming_cost_bytes(self) -> int:
        return 64


def test_write_read_round_trip():
    storage = MemoryStoragePlugin()
    payloads = {f"p{i}": bytes([i]) * (i + 1) for i in range(50)}
    write_reqs = [
        WriteReq(path=k, buffer_stager=_Stager(v)) for k, v in payloads.items()
    ]
    written = asyncio.run(
        execute_write_reqs(write_reqs, storage, memory_budget_bytes=1 << 20, rank=0)
    )
    assert written == sum(len(v) for v in payloads.values())
    assert storage.store == payloads

    sink = {}
    read_reqs = [
        ReadReq(path=k, buffer_consumer=_Consumer(sink, k)) for k in payloads
    ]
    read = asyncio.run(
        execute_read_reqs(read_reqs, storage, memory_budget_bytes=1 << 20, rank=0)
    )
    assert read == written
    assert sink == payloads


def test_budget_limits_concurrent_staging():
    storage = MemoryStoragePlugin()
    tracker = {"staging": 0, "max_staging": 0}
    # 100-byte buffers with a 250-byte budget: at most 2 staged at once.
    write_reqs = [
        WriteReq(path=f"p{i}", buffer_stager=_Stager(b"x" * 100, tracker))
        for i in range(10)
    ]
    asyncio.run(
        execute_write_reqs(write_reqs, storage, memory_budget_bytes=250, rank=0)
    )
    assert tracker["max_staging"] <= 2
    assert len(storage.store) == 10


def test_over_budget_buffer_still_progresses():
    storage = MemoryStoragePlugin()
    write_reqs = [WriteReq(path="big", buffer_stager=_Stager(b"x" * 1000))]
    written = asyncio.run(
        execute_write_reqs(write_reqs, storage, memory_budget_bytes=10, rank=0)
    )
    assert written == 1000


def test_write_error_propagates():
    class _FailingStorage(MemoryStoragePlugin):
        async def write(self, io_req: IOReq) -> None:
            raise IOError("disk on fire")

    with pytest.raises(IOError, match="disk on fire"):
        asyncio.run(
            execute_write_reqs(
                [WriteReq(path="p", buffer_stager=_Stager(b"x"))],
                _FailingStorage(),
                memory_budget_bytes=1 << 20,
                rank=0,
            )
        )


class _GatedStorage(MemoryStoragePlugin):
    """Every write blocks until the gate opens."""

    def __init__(self, cap: int) -> None:
        super().__init__()
        self.max_write_concurrency = cap
        self.gate = asyncio.Event()
        self.in_flight = 0
        self.max_in_flight = 0
        self.started_behind_the_gate = []

    async def write(self, io_req: IOReq) -> None:
        self.in_flight += 1
        self.max_in_flight = max(self.max_in_flight, self.in_flight)
        if not self.gate.is_set():
            self.started_behind_the_gate.append(io_req.path)
        await self.gate.wait()
        await super().write(io_req)
        self.in_flight -= 1


@pytest.mark.parametrize(
    "cap",
    sorted(
        {
            1,
            2,
            FSStoragePlugin.max_write_concurrency,
            StoragePlugin.max_write_concurrency,
        }
    ),
)
def test_write_streams_reach_the_cap_and_never_pass_it(cap):
    payloads = {f"p{i}": bytes([i]) * (i + 1) for i in range(2 * cap + 3)}
    write_reqs = [
        WriteReq(path=k, buffer_stager=_Stager(v)) for k, v in payloads.items()
    ]
    storage = _GatedStorage(cap)
    stats = {}

    async def _run():
        pipeline = asyncio.ensure_future(
            execute_write_reqs(
                write_reqs, storage, 1 << 20, rank=0, stats=stats
            )
        )
        # Every request stages at once and no write returns: the pipeline
        # comes to rest with every slot taken, and stays there.
        deadline = time.monotonic() + 30
        while storage.in_flight < cap and time.monotonic() < deadline:
            await asyncio.sleep(0.001)
        await asyncio.sleep(0.05)
        assert storage.in_flight == cap
        assert not pipeline.done()
        storage.gate.set()
        return await pipeline

    assert asyncio.run(_run()) == sum(len(v) for v in payloads.values())
    assert storage.max_in_flight == cap
    assert stats["write_concurrency"] == cap
    # The first `cap` requests found a free slot; every other one queued.
    queued = set(payloads) - set(storage.started_behind_the_gate)
    assert len(queued) == len(payloads) - cap
    waited = stats["ops"]["write_wait"]
    assert waited["count"] == len(queued)
    assert waited["bytes"] == sum(len(payloads[k]) for k in queued)
    assert waited["seconds"] >= 0.05
    assert stats["ops"]["write"]["count"] == len(payloads)


def test_memory_budget_env_override(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_PER_RANK_MEMORY_BUDGET_BYTES", "12345")
    assert get_process_memory_budget_bytes(NoOpCoordinator()) == 12345


def test_memory_budget_default():
    budget = get_process_memory_budget_bytes(NoOpCoordinator())
    assert 0 < budget <= 32 * 1024 * 1024 * 1024


def test_local_world_size():
    assert get_local_world_size(NoOpCoordinator()) == 1


class _DeferredConsumer(BufferConsumer):
    """Consumes instantly but holds a deferred reservation (the split-read
    assembly-buffer shape) released only when the test fires it."""

    def __init__(self, events, release_gate):
        self.events = events
        self.release_gate = release_gate
        self._release = None

    async def consume_buffer(self, buf, executor=None):
        self.events.append("A consumed")

        async def _later():
            await self.release_gate.wait()
            self.events.append("released")
            self._release(150)

        asyncio.ensure_future(_later())

    def get_consuming_cost_bytes(self) -> int:
        return 150

    def get_deferred_cost_bytes(self) -> int:
        return 150

    def set_cost_releaser(self, release):
        self._release = release


def test_deferred_cost_held_until_release():
    """A consumer's deferred reservation must stay charged after its
    consume task completes: a same-cost read behind it is only admitted
    once the consumer's releaser fires (ADVICE r4 medium — without this,
    concurrent split reads overrun the budget by the sum of their
    assembly buffers). All three requests share one cost so the
    largest-first dispatch sort keeps their list order (stable tie)."""
    events = []

    class _GatedConsumer(BufferConsumer):
        # Holds a never-refunded deferred reservation and keeps the
        # pipeline non-empty while it unblocks A's release — so the ONLY
        # budget that can admit B is A's released reservation.
        def __init__(self, release_gate):
            self.release_gate = release_gate

        async def consume_buffer(self, buf, executor=None):
            self.release_gate.set()
            await asyncio.sleep(0.02)
            events.append("C consumed")

        def get_consuming_cost_bytes(self) -> int:
            return 150

        def get_deferred_cost_bytes(self) -> int:
            return 150

        def set_cost_releaser(self, release):
            pass  # never released within this pipeline run

    class _RecordingConsumer(BufferConsumer):
        async def consume_buffer(self, buf, executor=None):
            events.append("B consumed")

        def get_consuming_cost_bytes(self) -> int:
            return 150

    async def _run():
        storage = MemoryStoragePlugin()
        for p in ("a", "b", "c"):
            await storage.write(IOReq(path=p, data=b"x"))
        gate = asyncio.Event()
        reqs = [
            ReadReq(path="a", buffer_consumer=_DeferredConsumer(events, gate)),
            ReadReq(path="c", buffer_consumer=_GatedConsumer(gate)),
            ReadReq(path="b", buffer_consumer=_RecordingConsumer()),
        ]
        # Budget admits A+C (300) but not B (needs 150 more); A's
        # consume refunds nothing (fully deferred), C's never refunds —
        # only A's explicit release can admit B.
        await execute_read_reqs(reqs, storage, memory_budget_bytes=350, rank=0)

    asyncio.run(_run())
    assert "released" in events and "B consumed" in events
    assert events.index("released") < events.index("B consumed")


def test_split_read_state_releases_assembly_cost_once():
    from torchsnapshot_tpu.io_preparer import _SplitObjectReadState

    sink = {}
    state = _SplitObjectReadState(10, _Consumer(sink, "k"))
    reqs = state.add_sub_reads("p", 4)
    assert len(reqs) == 3
    consumers = [r.buffer_consumer for r in reqs]
    assert consumers[0].get_deferred_cost_bytes() == 10
    assert consumers[1].get_deferred_cost_bytes() == 0
    calls = []
    consumers[0].set_cost_releaser(calls.append)

    async def _run():
        await consumers[0].consume_buffer(b"aaaa")
        await consumers[1].consume_buffer(b"bbbb")
        assert calls == []  # buffer still allocated: reservation held
        await consumers[2].consume_buffer(b"cc")

    asyncio.run(_run())
    assert calls == [10]  # released exactly once, on the last sub-read
    assert sink["k"] == b"aaaabbbbcc"


def test_streaming_split_defers_per_part_and_releases_on_drain():
    """The streaming split has NO host assembly buffer: it must not
    charge the whole object on the first sub-read (that serializes
    concurrent large restores), only defer each part's payload while it
    may sit in the out-of-order crc stash — or, post-fastlane, while
    the H2D overlap engine still holds it; the re-credit then arrives
    asynchronously once the transfer lands."""
    import zlib

    import jax
    import numpy as np

    from torchsnapshot_tpu.io_preparer import (
        _StreamingSplitState,
        _TargetRegion,
    )

    data = np.arange(4, dtype=np.float32).tobytes()  # 16 bytes
    crc = f"crc32:{zlib.crc32(data) & 0xFFFFFFFF:08x}"
    region = _TargetRegion([0], [4], np.dtype(np.float32))
    region.devices.append(jax.devices("cpu")[0])
    done = []
    state = _StreamingSplitState(
        16,
        region=region,
        dtype=np.dtype(np.float32),
        checksum=crc,
        on_done=lambda: done.append(1),
    )
    reqs = state.add_sub_reads("p", 8)
    c0, c1 = (r.buffer_consumer for r in reqs)
    assert c0.get_consuming_cost_bytes() == 8  # payload only, no nbytes
    assert c0.get_deferred_cost_bytes() == 8
    assert c1.get_deferred_cost_bytes() == 8
    released = []
    c0.set_cost_releaser(released.append)

    async def _run():
        # Out of order: the second part stashes (nothing drained yet —
        # its crc hold can only drop once the prefix lands).
        await c1.consume_buffer(data[8:16])
        await c0.consume_buffer(data[0:8])

    asyncio.run(_run())
    # Completion (and the budget re-credit) is asynchronous: the
    # overlap engine's done-callback fires it once both parts' H2D
    # transfers land.
    deadline = time.monotonic() + 30
    while not done and time.monotonic() < deadline:
        time.sleep(0.005)
    assert done == [1]
    assert sum(released) == 16  # both parts re-credited exactly once
    assert region.device_chunks is not None
    assert len(region.device_chunks) == 2
