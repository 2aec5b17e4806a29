"""What a restore above HBM/2 says of itself: a ``restore.
release_template`` span around the template's release, a ``restore.
device_budget_wait`` span for every consume that ``scheduler.
execute_read_reqs`` held back because the device budget had no room for
its chunks, and ``device_budget_waits`` / ``device_budget_wait_s`` in the
restore's report beside ``template_released_bytes``. All under a faked
device budget (the library's own knob) on the CPU."""

import asyncio
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import torchsnapshot_tpu.io_preparer as iop
from torchsnapshot_tpu import PytreeStateful, Snapshot, tracing
from torchsnapshot_tpu.io_types import BufferConsumer, IOReq, ReadReq
from torchsnapshot_tpu.scheduler import execute_read_reqs
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin


def _spans(path):
    """name -> [(begin_us, end_us, args)] of a flushed trace."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    begins, out = {}, {}
    for ev in events:
        if ev.get("ph") == "b":
            begins[ev["id"]] = ev
        elif ev.get("ph") == "e" and ev["id"] in begins:
            b = begins.pop(ev["id"])
            out.setdefault(b["name"], []).append((b["ts"], ev["ts"], b.get("args", {})))
    return out


def _report(path):
    with open(os.path.join(path, ".report.restore.json")) as f:
        return json.load(f)["ranks"][0]


@pytest.fixture
def traced(tmp_path):
    path = str(tmp_path / "trace.json")
    tracing.enable(path)
    yield path
    if tracing.enabled():
        tracing.disable()


@pytest.fixture
def small_scale(monkeypatch):
    """1 MiB format chunks, 256 KiB sub-reads (``test_streaming_restore``)."""
    monkeypatch.setattr(iop, "MAX_CHUNK_SIZE_BYTES", 1 << 20)
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(256 << 10))


def _arr(nbytes, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(nbytes // 4), jnp.float32)


class _DevConsumer(BufferConsumer):
    """A consume that deposits ``dcost`` device bytes, holds them for
    ``hold_s`` and gives them back."""

    def __init__(self, dcost, hold_s=0.0):
        self.dcost, self.hold_s, self._release = dcost, hold_s, None

    async def consume_buffer(self, buf, executor=None):
        await asyncio.sleep(self.hold_s)
        if self._release is not None:
            self._release(self.dcost)

    def get_consuming_cost_bytes(self):
        return 1

    def get_device_cost_bytes(self):
        return self.dcost

    def set_device_cost_releaser(self, release):
        self._release = release


class _OrderedStorage(MemoryStoragePlugin):
    delays = {"a": 0.0, "b": 0.01, "c": 0.02}

    async def read(self, io_req):
        await asyncio.sleep(self.delays[io_req.path])
        await super().read(io_req)


def _run_three(device_budget_bytes, stats):
    """A holds 80 of the budget for 60 ms; B (50) and C (30) become
    consumable 10 and 20 ms in."""

    async def run():
        storage = _OrderedStorage()
        for p in "abc":
            await storage.write(IOReq(path=p, data=b"x"))
        await execute_read_reqs(
            [
                ReadReq(path="a", buffer_consumer=_DevConsumer(80, hold_s=0.06)),
                ReadReq(path="b", buffer_consumer=_DevConsumer(50)),
                ReadReq(path="c", buffer_consumer=_DevConsumer(30)),
            ],
            storage,
            memory_budget_bytes=1 << 20,
            rank=0,
            device_budget_bytes=device_budget_bytes,
            stats=stats,
        )

    asyncio.run(run())


def test_every_admission_held_back_on_the_device_budget_is_a_span_and_a_count(traced):
    stats = {}
    _run_three(100, stats)
    tracing.flush()
    waits = _spans(traced)["restore.device_budget_wait"]
    # B and C both waited for A's 80 to come back; A never did.
    assert sorted(args["path"] for _, _, args in waits) == ["b", "c"]
    assert {args["bytes"] for _, _, args in waits} == {50, 30}
    assert stats["device_budget_waits"] == 2
    lengths = [(end - begin) / 1e6 for begin, end, _ in waits]
    # each from when it could first have been consumed until A gave way:
    # about 50 and 40 ms; side by side, so the sum is thread-seconds
    assert all(0.02 < s < 0.5 for s in lengths)
    assert stats["device_budget_wait_s"] == pytest.approx(sum(lengths), abs=2e-3)


def test_a_budget_with_room_holds_nothing_back_and_says_so(traced):
    stats = {}
    _run_three(1000, stats)
    tracing.flush()
    assert "restore.device_budget_wait" not in _spans(traced)
    assert stats["device_budget_waits"] == 0 and stats["device_budget_wait_s"] == 0.0
    unbounded = {}
    _run_three(None, unbounded)
    assert unbounded["device_budget_waits"] == 0


def test_a_restore_under_a_faked_budget_reports_its_release_and_its_waits(
    tmp_path, small_scale, monkeypatch, traced
):
    """Two Statefuls of 3 MiB regions under a device of 9 MiB, faked:
    each template crowds it and is let go inside a ``restore.
    release_template`` span that names its bytes; region b's first
    consume is held back until region a's transient half is released
    (``test_streaming_restore_respects_device_budget``), which the
    report counts and the trace shows."""
    region = 3 << 20
    a, b = _arr(region, 2), _arr(region, 3)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": PytreeStateful({"a": a, "b": b})})
    monkeypatch.setenv("TPUSNAPSHOT_DEVICE_BUDGET_BYTES", str(9 << 20))
    # Parts of both regions in flight together, so that b's first payload
    # arrives while a is still in assembly (through the fs plug-in's two
    # streams b's may arrive after a has been assembled: no wait at all).
    monkeypatch.setattr(FSStoragePlugin, "max_read_concurrency", 16)
    target = {"m": PytreeStateful({"a": jnp.zeros_like(a), "b": jnp.zeros_like(b)})}
    Snapshot(path).restore(target)
    tracing.flush()
    np.testing.assert_array_equal(np.asarray(target["m"].tree["a"]), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(target["m"].tree["b"]), np.asarray(b))
    spans, report = _spans(traced), _report(path)
    (release,) = spans["restore.release_template"]
    assert release[2]["bytes"] == 2 * region == report["template_released_bytes"]
    assert release[2]["key"] == "m" and release[1] >= release[0]
    # the release lies inside the plan stretch, before the first read
    (plan,) = spans["restore.plan"]
    assert plan[0] <= release[0] and release[1] <= plan[1]
    assert min(begin for begin, _, _ in spans["read"]) >= release[1]
    waits = spans["restore.device_budget_wait"]
    assert report["device_budget_waits"] == len(waits) >= 1
    assert report["device_budget_wait_s"] == pytest.approx(
        sum(end - begin for begin, end, _ in waits) / 1e6, abs=2e-3
    )
    assert all(args["bytes"] > 0 and args["path"] for _, _, args in waits)


def test_a_restore_with_room_reports_no_release_and_no_wait(tmp_path, traced):
    a = _arr(1 << 16, 4)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": PytreeStateful({"a": a})})
    Snapshot(path).restore({"m": PytreeStateful({"a": jnp.zeros_like(a)})})
    tracing.flush()
    spans, report = _spans(traced), _report(path)
    assert "restore.plan" in spans
    assert "restore.release_template" not in spans
    assert "restore.device_budget_wait" not in spans
    assert report["template_released_bytes"] == 0
    assert report["device_budget_waits"] == 0 and report["device_budget_wait_s"] == 0.0


def test_a_streamed_leaf_s_chunks_are_gone_when_restore_returns(tmp_path, small_scale):
    """With the cyclic collector off, so that only reference counts free
    anything: when ``restore`` returns, the device holds the restored
    arrays and nothing else of the restore's. (The plan kept every
    overlap-engine future, and a resolved future keeps its device chunk:
    a state of 57 % of HBM then stood on the chip at 95 % until the next
    ``gc.collect()``, and the first resumed step found no room.)"""
    import gc

    a, b = _arr(3 << 20, 5), _arr(2 << 20, 6)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": PytreeStateful({"a": a, "b": b})})
    gc.collect()
    before = {id(x) for x in jax.live_arrays()}

    def new_bytes():
        return sum(x.nbytes for x in jax.live_arrays() if id(x) not in before)

    gc.disable()
    try:
        target = {"m": PytreeStateful({"a": jnp.zeros_like(a), "b": jnp.zeros_like(b)})}
        Snapshot(path).restore(target)
        assert new_bytes() == a.nbytes + b.nbytes
    finally:
        gc.enable()
    np.testing.assert_array_equal(np.asarray(target["m"].tree["a"]), np.asarray(a))
