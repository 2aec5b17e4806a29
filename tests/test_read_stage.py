"""The read stage of ``scheduler.execute_read_reqs`` feeds itself: a
finished read is followed by the next read of the scheduler's order
without a turn of the loop that admits the consumes, through no more
streams than the plug-in's ``max_read_concurrency``; and what a restore
guarantees is what it guaranteed before (every byte verified before an
array is exposed, a failed read fails the restore, budgets hold).

No test here reads a clock: plug-in reads block on events the test
sets, and every wait has a limit that only a hang reaches.
"""

import asyncio
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import torchsnapshot_tpu.scheduler as sched
from torchsnapshot_tpu import Snapshot, StateDict, staging_pool
from torchsnapshot_tpu import faultline as fl
from torchsnapshot_tpu import snapshot as snapshot_mod
from torchsnapshot_tpu.io_types import BufferConsumer, IOReq, ReadReq
from torchsnapshot_tpu.scheduler import execute_read_reqs
from torchsnapshot_tpu.serialization import StreamingCrc32
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

_LIMIT_S = 60  # of a wait that only a hang reaches
_PART = 4096


class _Gates:
    """What the gated plug-ins record, and the events their reads wait
    on: one a read, keyed by (path, byte_range)."""

    def __init__(self):
        self.changed = threading.Condition()
        self.started = []
        self.in_flight = 0
        self.max_in_flight = 0
        self._events = {}
        self._open = False

    def enter(self, key):
        with self.changed:
            self.started.append(key)
            self.in_flight += 1
            self.max_in_flight = max(self.max_in_flight, self.in_flight)
            event = self._events.setdefault(key, threading.Event())
            if self._open:
                event.set()
            self.changed.notify_all()
        return event

    def leave(self):
        with self.changed:
            self.in_flight -= 1
            self.changed.notify_all()

    def release(self, key):
        with self.changed:
            self._events.setdefault(key, threading.Event()).set()

    def open(self):
        with self.changed:
            self._open = True
            for event in self._events.values():
                event.set()

    def wait_started(self, count):
        with self.changed:
            reached = self.changed.wait_for(
                lambda: len(self.started) >= count, _LIMIT_S
            )
        assert reached, f"{len(self.started)} reads started, waited for {count}"


async def _gated(gates, key, read):
    event = gates.enter(key)
    try:
        opened = await asyncio.get_running_loop().run_in_executor(
            None, event.wait, _LIMIT_S
        )
        assert opened, f"nobody opened the gate of {key}"
        await read()
    finally:
        gates.leave()


class _GatedFS(FSStoragePlugin):
    """Payload reads wait for their gate; the snapshot's own documents
    (dot-prefixed) pass."""

    def __init__(self, root, gates, fanout):
        super().__init__(root)
        self.max_read_concurrency = fanout
        self._gates = gates

    async def read(self, io_req):
        if io_req.path.startswith("."):
            return await super().read(io_req)
        await _gated(
            self._gates,
            (io_req.path, io_req.byte_range),
            lambda: FSStoragePlugin.read(self, io_req),
        )


class _GatedMemory(MemoryStoragePlugin):
    def __init__(self, gates, fanout):
        super().__init__()
        self.max_read_concurrency = fanout
        self._gates = gates

    async def read(self, io_req):
        await _gated(
            self._gates,
            (io_req.path, io_req.byte_range),
            lambda: MemoryStoragePlugin.read(self, io_req),
        )


def _spy_budget_cells(monkeypatch):
    cells = []

    class _SpyCell(sched._BudgetCell):
        def __init__(self, value):
            super().__init__(value)
            self.initial = value
            self.min_seen = value
            cells.append(self)

        def charge(self, nbytes):
            super().charge(nbytes)
            self.min_seen = min(self.min_seen, self.value)

    monkeypatch.setattr(sched, "_BudgetCell", _SpyCell)
    return cells


def _scheduler_order(read_reqs):
    """Largest logical object first, an object's parts together and in
    order (a stable sort on the whole object's size)."""

    def size(r):
        key = getattr(r.buffer_consumer, "sort_key_bytes", None)
        return key if key is not None else r.buffer_consumer.get_consuming_cost_bytes()

    return [
        (r.path, r.byte_range) for r in sorted(read_reqs, key=lambda r: -size(r))
    ]


def _in_a_thread(fn):
    outcome = {}

    def run():
        try:
            outcome["value"] = fn()
        except BaseException as e:  # handed to the test's thread
            outcome["error"] = e

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    return thread, outcome


def _restore_report(path):
    with open(os.path.join(path, ".report.restore.json")) as f:
        return json.load(f)["ranks"][0]


@pytest.mark.parametrize("fanout", [1, 4, 16])
def test_a_finished_read_starts_the_next_of_the_order_with_the_loop_held(
    tmp_path, monkeypatch, fanout
):
    """While a consume body holds a streamed object's lock across its
    fold and the thread of the loop that admits consumes is stuck,
    completing one read starts the next read of the scheduler's order:
    never more than the fan-out in flight, in the largest-first order,
    with the host budget never overdrawn."""
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(_PART))
    host_budget = (fanout + 8) * _PART
    monkeypatch.setenv("TPUSNAPSHOT_PER_RANK_MEMORY_BUDGET_BYTES", str(host_budget))
    rng = np.random.default_rng(fanout)
    state = {
        "small": jnp.asarray(rng.standard_normal(12 * _PART // 4), jnp.float32),
        "large": jnp.asarray(rng.standard_normal(40 * _PART // 4), jnp.float32),
        "tiny": jnp.asarray(rng.standard_normal(16), jnp.float32),
    }
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(**state)})

    gates = _Gates()
    monkeypatch.setattr(
        snapshot_mod, "url_to_storage_plugin", lambda p: _GatedFS(p, gates, fanout)
    )
    cells = _spy_budget_cells(monkeypatch)
    planned = []
    real_execute = snapshot_mod.execute_read_reqs

    async def planning(read_reqs, *args, **kwargs):
        planned.extend(_scheduler_order(read_reqs))
        return await real_execute(read_reqs, *args, **kwargs)

    monkeypatch.setattr(snapshot_mod, "execute_read_reqs", planning)

    # The first fold of the restore stops inside the stream's lock.
    fold_held, fold_gate = threading.Event(), threading.Event()
    real_update = StreamingCrc32.update

    def held_update(self, chunk):
        if not fold_held.is_set():
            fold_held.set()
            assert fold_gate.wait(_LIMIT_S)
        return real_update(self, chunk)

    monkeypatch.setattr(StreamingCrc32, "update", held_update)

    # The second payload that reaches the consumes' loop stops its thread.
    loop_held, loop_gate = threading.Event(), threading.Event()
    real_observe = sched._observe_op
    reads_seen = []

    def held_observe(ops, op, *args, **kwargs):
        if op == "read":
            reads_seen.append(op)
            if len(reads_seen) == 2:
                loop_held.set()
                assert loop_gate.wait(_LIMIT_S)
        return real_observe(ops, op, *args, **kwargs)

    monkeypatch.setattr(sched, "_observe_op", held_observe)

    target = {"m": StateDict(**jax.tree.map(jnp.zeros_like, state))}
    thread, outcome = _in_a_thread(lambda: Snapshot(path).restore(target))
    try:
        gates.wait_started(fanout)
        order = list(planned)
        assert len(order) == 40 + 12 + 1
        assert order[0][0].endswith("large") and order[40][0].endswith("small")
        assert gates.started == order[:fanout]

        gates.release(order[0])
        assert fold_held.wait(_LIMIT_S), "no consume reached its fold"
        gates.wait_started(fanout + 1)
        gates.release(order[1])
        assert loop_held.wait(_LIMIT_S), "the second payload never arrived"
        # A consume body holds the large object's lock inside its fold,
        # and the loop's thread is stuck: the read stage goes on alone.
        for done in range(2, 6):
            gates.wait_started(fanout + done)
            assert gates.started == order[: fanout + done]
            assert gates.in_flight == fanout
            gates.release(order[done])
        gates.wait_started(fanout + 6)
        assert gates.started == order[: fanout + 6]
        assert gates.max_in_flight == fanout
    finally:
        loop_gate.set()
        fold_gate.set()
        gates.open()
        thread.join(_LIMIT_S)
    assert not thread.is_alive()
    assert "error" not in outcome, outcome.get("error")
    for name, value in state.items():
        assert np.asarray(target["m"][name]).tobytes() == np.asarray(value).tobytes()
    assert gates.started == order
    assert gates.max_in_flight == fanout
    host_cells = [c for c in cells if c.initial == host_budget]
    assert len(host_cells) == 1
    # Every part fits the budget, so nothing was admitted by force.
    assert 0 <= host_cells[0].min_seen <= host_budget - fanout * _PART
    assert host_cells[0].value == host_budget
    report = _restore_report(path)
    assert report["read_streams"] == fanout
    assert report["read_idle_s"] >= 0.0


class _Sink(BufferConsumer):
    def __init__(self, nbytes):
        self.nbytes = nbytes
        self.got = None

    async def consume_buffer(self, buf, executor=None):
        self.got = bytes(buf)

    def get_consuming_cost_bytes(self):
        return self.nbytes


def test_reads_that_never_leave_a_gap_report_no_idle_second():
    """Two streams, each read let go only once its successor is in
    flight: from the first read issued to the last returned some read is
    always in flight, so ``read_idle_s`` is exactly 0."""
    gates = _Gates()
    storage = _GatedMemory(gates, fanout=2)
    names = [f"obj{i}" for i in range(7)]
    sinks = {name: _Sink(100 - i) for i, name in enumerate(names)}
    stats = {}

    async def run():
        for name, sink in sinks.items():
            await storage.write(IOReq(path=name, data=name.encode() * sink.nbytes))
        return await execute_read_reqs(
            [ReadReq(path=n, buffer_consumer=s) for n, s in sinks.items()],
            storage,
            memory_budget_bytes=1 << 20,
            rank=0,
            stats=stats,
        )

    thread, outcome = _in_a_thread(lambda: asyncio.run(run()))
    try:
        for i, name in enumerate(names):
            gates.wait_started(min(i + 2, len(names)))
            gates.release((name, None))
    finally:
        gates.open()
        thread.join(_LIMIT_S)
    assert not thread.is_alive() and "error" not in outcome, outcome.get("error")
    assert [key[0] for key in gates.started] == names  # largest first
    assert gates.max_in_flight == 2
    assert all(s.got == n.encode() * s.nbytes for n, s in sinks.items())
    assert stats["read_idle_s"] == 0.0
    assert stats["read_streams"] == 2
    assert stats["ops"]["read"]["count"] == len(names)


def test_a_head_above_the_budget_waits_for_the_consumes_then_goes_alone():
    """The forced admission that exists: a request dearer than the whole
    budget is issued once nothing is in flight anywhere, not before."""
    gates = _Gates()
    storage = _GatedMemory(gates, fanout=4)
    sinks = {"big": _Sink(500), "huge": _Sink(400)}
    stats = {}

    async def run():
        for name in sinks:
            await storage.write(IOReq(path=name, data=b"x"))
        await execute_read_reqs(
            [ReadReq(path=n, buffer_consumer=s) for n, s in sinks.items()],
            storage,
            memory_budget_bytes=300,
            rank=0,
            stats=stats,
        )

    thread, outcome = _in_a_thread(lambda: asyncio.run(run()))
    try:
        gates.wait_started(1)
        assert gates.started == [("big", None)] and gates.in_flight == 1
        gates.release(("big", None))
        gates.wait_started(2)
        # "huge" started only after "big" was read AND consumed.
        assert sinks["big"].got == b"x"
        gates.release(("huge", None))
    finally:
        gates.open()
        thread.join(_LIMIT_S)
    assert not thread.is_alive() and "error" not in outcome, outcome.get("error")
    assert gates.max_in_flight == 1
    assert stats["budget_high_water_bytes"] == 500


@pytest.mark.parametrize(
    "route", ["streamed", "streamed_into_pool", "host_assembled"]
)
def test_a_corrupted_part_raises_before_any_array_is_exposed(
    tmp_path, monkeypatch, route
):
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(_PART))
    monkeypatch.setenv("TPUSNAPSHOT_STRICT_INTEGRITY", "1")
    if route == "streamed_into_pool":
        # Puts that copy (the chunked path's concatenate): the parts are
        # read into pooled buffers, which all go back after the failure.
        monkeypatch.setenv("TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER", "1")
        monkeypatch.setenv("TPUSNAPSHOT_H2D_CHUNK_BYTES", str(_PART // 4))
        staging_pool.reset_staging_pool()
    values = np.arange(8 * _PART // 4, dtype=np.float32)
    saved = values if route == "host_assembled" else jnp.asarray(values)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(w=saved, other=jnp.ones((8,)))})
    obj = tmp_path / "snap" / "0" / "m" / "w"
    raw = bytearray(obj.read_bytes())
    raw[5 * _PART + 8 : 5 * _PART + 12] = b"\xde\xad\xbe\xef"
    obj.write_bytes(bytes(raw))

    exposed = []

    class _Target(StateDict):
        def load_state_dict(self, sd):
            exposed.append(sd)
            super().load_state_dict(sd)

    template = (
        np.zeros_like(saved) if route == "host_assembled" else jnp.zeros_like(saved)
    )
    target = _Target(w=template, other=jnp.zeros((8,)))
    with pytest.raises(RuntimeError, match="[Cc]hecksum"):
        Snapshot(path).restore({"m": target})
    assert exposed == []
    assert not np.asarray(target["w"]).any() and not np.asarray(target["other"]).any()
    if route == "streamed_into_pool":
        # The last transfers land after the restore has raised.
        pool = staging_pool.get_staging_pool()
        deadline = time.monotonic() + _LIMIT_S
        while pool.stats()["in_use_bytes"] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pool.stats()["in_use_bytes"] == 0
        assert pool.stats()["free_bytes"] > 0
        staging_pool.reset_staging_pool()


@pytest.mark.faultline
@pytest.mark.parametrize("kind", ["permanent", "crash"])
def test_a_faultline_read_fault_surfaces_from_the_read_stage(
    tmp_path, monkeypatch, kind
):
    monkeypatch.setenv("TPUSNAPSHOT_STORAGE_RETRIES", "0")
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(_PART))
    w = jnp.arange(4 * _PART // 4, dtype=jnp.float32)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(w=w, v=jnp.ones((8,)))})
    schedule = fl.FaultSchedule()
    if kind == "permanent":
        schedule.permanent(op="read", path="*m/w", nth=3)
        raised = fl.InjectedPermanentError
    else:
        schedule.crash_on(op="read", path="*m/w", nth=3)
        raised = fl.SimulatedCrash
    target = StateDict(w=jnp.zeros_like(w), v=jnp.zeros((8,)))
    with fl.inject(schedule) as ctl:
        with pytest.raises(raised):
            Snapshot(path).restore({"m": target})
    assert sum(ctl.fault_counts().values()) >= 1
    assert not np.asarray(target["w"]).any()


def test_a_resharding_restore_is_bit_exact(tmp_path, monkeypatch):
    """Saved under tp=4, restored onto dp=2 x tp=2: each saved shard
    lands on two devices."""
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs four devices")
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(_PART))
    rng = np.random.default_rng(7)
    w = rng.standard_normal((64, 256)).astype(np.float32)
    b = rng.standard_normal((256,)).astype(np.float32)
    tp4 = Mesh(np.array(devices[:4]).reshape(1, 4), ("dp", "tp"))
    saved = StateDict(
        w=jax.device_put(w, NamedSharding(tp4, P(None, "tp"))),
        b=jax.device_put(b, NamedSharding(tp4, P())),
    )
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": saved})
    dp2tp2 = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
    target = StateDict(
        w=jax.device_put(np.zeros_like(w), NamedSharding(dp2tp2, P(None, "tp"))),
        b=jax.device_put(np.zeros_like(b), NamedSharding(dp2tp2, P())),
    )
    Snapshot(path).restore({"m": target})
    assert np.asarray(target["w"]).tobytes() == w.tobytes()
    assert np.asarray(target["b"]).tobytes() == b.tobytes()
    assert target["w"].sharding.is_equivalent_to(
        NamedSharding(dp2tp2, P(None, "tp")), 2
    )
    report = _restore_report(path)
    assert report["read_streams"] == FSStoragePlugin.max_read_concurrency
    assert report["read_idle_s"] >= 0.0


def test_a_restore_under_a_tight_device_budget_is_bit_exact(tmp_path, monkeypatch):
    """Three streamed regions under a device budget with room for one at
    a time: consumes wait their turn on the loop's thread, reads do not."""
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(_PART))
    region = 4 * _PART
    monkeypatch.setenv("TPUSNAPSHOT_DEVICE_BUDGET_BYTES", str(3 * region))
    # All three regions' parts in flight together: the second region's
    # first payload arrives while the first is still in assembly.
    monkeypatch.setattr(FSStoragePlugin, "max_read_concurrency", 16)
    rng = np.random.default_rng(11)
    state = {
        name: jnp.asarray(rng.standard_normal(region // 4), jnp.float32)
        for name in "abc"
    }
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(**state)})
    target = StateDict(**jax.tree.map(jnp.zeros_like, state))
    Snapshot(path).restore({"m": target})
    for name, value in state.items():
        assert np.asarray(target[name]).tobytes() == np.asarray(value).tobytes()
    report = _restore_report(path)
    assert report["device_budget_waits"] >= 1
    assert report["read_streams"] == 16


class _DeferringSink(BufferConsumer):
    """Half of the cost is given back by another thread some time after
    the consume has ended, as the overlap engine's callbacks do."""

    def __init__(self, nbytes, late):
        self.nbytes, self.late, self.consumed, self._release = nbytes, late, 0, None

    async def consume_buffer(self, buf, executor=None):
        await asyncio.get_running_loop().run_in_executor(executor, len, buf)
        self.consumed += 1
        self.late.submit(self._release, self.nbytes // 2)

    def get_consuming_cost_bytes(self):
        return self.nbytes

    def get_deferred_cost_bytes(self):
        return self.nbytes // 2

    def set_cost_releaser(self, release):
        self._release = release


def test_stress_every_request_is_read_and_consumed_once_within_the_budget(monkeypatch):
    """Three threads charge and release one cell (the read stage, the
    consumes' loop, the late releasers) under a switch interval of 10 us:
    a lost update would leave the cell off its start, overdraw it, or
    strand the head of the queue."""
    import sys
    from concurrent.futures import ThreadPoolExecutor

    cells = _spy_budget_cells(monkeypatch)
    gates = _Gates()
    gates.open()
    storage = _GatedMemory(gates, fanout=16)
    budget = 64
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor(max_workers=32) as late:
            sinks = {f"o{i}": _DeferringSink(8 + i % 9, late) for i in range(600)}

            async def run():
                for name in sinks:
                    await storage.write(IOReq(path=name, data=b"y" * 8))
                return await execute_read_reqs(
                    [ReadReq(path=n, buffer_consumer=s) for n, s in sinks.items()],
                    storage,
                    memory_budget_bytes=budget,
                    rank=0,
                )

            thread, outcome = _in_a_thread(lambda: asyncio.run(run()))
            thread.join(_LIMIT_S)
            assert not thread.is_alive(), "the pipeline hung"
    finally:
        sys.setswitchinterval(interval)
    assert outcome.get("value") == 600 * 8, outcome.get("error")
    assert all(s.consumed == 1 for s in sinks.values())
    assert len(gates.started) == 600 and gates.max_in_flight <= 16
    (host,) = [c for c in cells if c.initial == budget]
    assert host.min_seen >= 0 and host.value == budget

