"""An async take clones its cut on the device only where the clones fit
beside the process's own peak (``io_preparer.clone_fit``,
``snapshot._prestage_write_reqs``): the device's ``peak_bytes_in_use``,
the clones and one more copy of its largest shard within
``bytes_limit``. Otherwise it stages to the host without the attempt.
The CPU devices here report no memory, so the memory a device reports
is faked through ``io_preparer._peak_and_limit``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchsnapshot_tpu import PytreeStateful, Snapshot, io_preparer, telemetry, tracing
from torchsnapshot_tpu.telemetry import metrics as metric_names

PEAK = 10_000_000
LEAVES = {"w": (64, 64), "v": (64, 32), "b": (16,)}  # float32
CLONE = sum(4 * int(np.prod(s)) for s in LEAVES.values())  # 24640 B
LARGEST = 4 * 64 * 64  # the margin: one more copy of the largest shard


def _tree():
    keys = jax.random.split(jax.random.key(0), len(LEAVES))
    return {
        name: jax.random.normal(key, shape)
        for key, (name, shape) in zip(keys, LEAVES.items())
    }


def _equal(a, b):
    return all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


@pytest.fixture(autouse=True)
def no_clone_marks(monkeypatch):
    """Each test starts from a process whose library never cloned."""
    monkeypatch.setattr(io_preparer, "_clone_marks", {})


@pytest.fixture
def device_memory(monkeypatch):
    """``set(limit, peak=PEAK)`` makes every device report that much."""

    def set_to(limit, peak=PEAK):
        monkeypatch.setattr(io_preparer, "_peak_and_limit", lambda device: (peak, limit))

    return set_to


@pytest.fixture
def clone_calls(monkeypatch):
    calls = []
    real = io_preparer.device_clone

    def counting(arrays):
        calls.append(len(arrays))
        return real(arrays)

    monkeypatch.setattr(io_preparer, "device_clone", counting)
    return calls


def _take(tmp_path, caplog, stage="auto", tree=None):
    tree = _tree() if tree is None else tree
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
    with open(trace) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"] if e.get("ph") == "b"]
    restored = PytreeStateful(jax.tree.map(jnp.zeros_like, tree), convert=True)
    Snapshot(path).restore({"s": restored})
    assert _equal(restored.tree, tree)
    warned = sum("falling back to host staging" in r.getMessage() for r in caplog.records)
    return rank, names, warned


def _gauges():
    values = telemetry.snapshot()
    return tuple(
        values[name]
        for name in (
            metric_names.CAPTURE_CLONE_BYTES,
            metric_names.CAPTURE_DEVICE_PEAK_BYTES,
            metric_names.CAPTURE_DEVICE_LIMIT_BYTES,
        )
    )


@pytest.mark.parametrize(
    "spare,route",
    [(0, "device_clones"), (-1, "host_staging")],
    ids=["fits_to_the_byte", "one_byte_short"],
)
def test_the_clones_are_taken_only_where_they_fit_beside_the_peak(
    tmp_path, caplog, device_memory, clone_calls, spare, route
):
    limit = PEAK + CLONE + LARGEST + spare
    device_memory(limit)
    rank, spans, warned = _take(tmp_path, caplog)
    assert rank["capture_route"] == route
    assert rank["capture_clones_fit"] is (route == "device_clones")
    assert (
        rank["capture_clone_bytes"], rank["capture_device_peak_bytes"],
        rank["capture_device_limit_bytes"],
    ) == (CLONE, PEAK, limit)
    assert _gauges() == (CLONE, PEAK, limit)
    assert "capture.fit" in spans
    if route == "device_clones":
        assert clone_calls == [len(LEAVES)] and warned == 0
        assert "capture.clone" in spans and "capture_host_stage" not in spans
    else:
        # no attempt: nothing was cloned to find out
        assert clone_calls == [] and warned == 1
        assert "capture.clone" not in spans and "capture_host_stage" in spans
        assert rank["capture_host_staged_bytes"] == CLONE


def test_stage_device_still_raises_where_the_clones_do_not_fit(
    tmp_path, device_memory, clone_calls
):
    device_memory(PEAK + CLONE)  # without the margin
    with pytest.raises(RuntimeError, match="stage='device'"):
        Snapshot.async_take(
            str(tmp_path / "snap"), {"s": PytreeStateful(_tree(), convert=True)},
            stage="device",
        )
    assert clone_calls == []


def test_a_device_that_reports_no_memory_clones_as_before(
    tmp_path, caplog, monkeypatch, clone_calls
):
    rank, spans, warned = _take(tmp_path, caplog)
    assert rank["capture_route"] == "device_clones" and clone_calls == [len(LEAVES)]
    assert rank["capture_clones_fit"] is None
    assert rank["capture_device_peak_bytes"] is None
    assert rank["capture_device_limit_bytes"] is None
    assert _gauges() == (CLONE, 0, 0) and warned == 0
    # and a clone that runs out of memory still falls back, as before
    monkeypatch.setattr(io_preparer, "device_clone", lambda arrays: None)
    rank, spans, warned = _take(tmp_path / "again", caplog)
    assert rank["capture_route"] == "host_staging" and warned == 1
    assert "capture.clone" in spans


def test_stage_host_makes_no_fit_test(tmp_path, caplog, device_memory, clone_calls):
    device_memory(PEAK + CLONE + LARGEST)
    rank, spans, warned = _take(tmp_path, caplog, stage="host")
    assert rank["capture_route"] == "host_staging" and clone_calls == []
    assert rank["capture_clones_fit"] is None and "capture.fit" not in spans
    assert warned == 0


def test_the_fit_is_decided_on_the_device_where_it_is_worst(
    tmp_path, caplog, monkeypatch, clone_calls
):
    """A leaf sharded over two devices and a leaf on the first alone:
    each device counts its own shards, and one device that cannot hold
    its share sends the whole take to the host."""
    devices = jax.devices()[:2]
    if len(devices) < 2:
        pytest.skip("needs two devices")
    mesh = jax.sharding.Mesh(np.array(devices), ("x",))
    sharded = jax.device_put(
        jnp.ones((64, 32)), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("x"))
    )
    alone = jax.device_put(jnp.ones((16, 16)), devices[0])
    tree = {"sharded": sharded, "alone": alone}
    first, second, margin = 4 * 32 * 32 + 4 * 16 * 16, 4 * 32 * 32, 4 * 32 * 32
    limits = {devices[0]: PEAK + first + margin, devices[1]: PEAK + second + 2 * margin}
    monkeypatch.setattr(io_preparer, "_peak_and_limit", lambda d: (PEAK, limits[d]))
    rank, _, _ = _take(tmp_path, caplog, tree=tree)
    assert rank["capture_route"] == "device_clones"
    # the first device carries more and has less room: the fit is worst there
    assert rank["capture_clone_bytes"] == first
    assert rank["capture_device_limit_bytes"] == limits[devices[0]]
    limits[devices[1]] = PEAK + second + margin - 1
    clone_calls.clear()
    rank, _, warned = _take(tmp_path / "again", caplog, tree=tree)
    assert rank["capture_route"] == "host_staging" and clone_calls == [] and warned == 1


@pytest.mark.parametrize(
    "rise,second",
    [(0, "device_clones"), (1, "host_staging")],
    ids=["own_clones_not_counted", "a_later_rise_counts_whole"],
)
def test_a_second_save_does_not_count_the_first_save_s_clones(
    tmp_path, caplog, monkeypatch, rise, second
):
    """Two saves in one process. The first one's clones live beside the
    steps that follow it, so the device's peak rises by the clones and
    stays there. The second save's clones still fit beside the process's
    own need; where the peak rose again after the first save's clones
    were gone, that rise is the process's own and counts whole."""
    memory = {"peak": PEAK}
    limit = PEAK + CLONE + LARGEST
    monkeypatch.setattr(io_preparer, "_peak_and_limit", lambda d: (memory["peak"], limit))
    real = io_preparer.device_clone

    def cloning_beside_a_step(arrays):
        memory["peak"] = max(memory["peak"], PEAK + CLONE)
        return real(arrays)

    monkeypatch.setattr(io_preparer, "device_clone", cloning_beside_a_step)
    rank, _, warned = _take(tmp_path, caplog)
    assert rank["capture_route"] == "device_clones" and warned == 0
    assert memory["peak"] == PEAK + CLONE
    memory["peak"] += rise
    rank, _, warned = _take(tmp_path / "again", caplog)
    assert rank["capture_route"] == second
    assert rank["capture_device_peak_bytes"] == (PEAK if rise == 0 else PEAK + CLONE + rise)
    assert warned == (second == "host_staging")


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        if isinstance(self.stats, Exception):
            raise self.stats
        return self.stats


@pytest.mark.parametrize(
    "stats,read",
    [
        ({"peak_bytes_in_use": 7, "bytes_limit": 16, "bytes_in_use": 3}, (7, 16)),
        ({"bytes_in_use": 3, "bytes_limit": 16}, None),  # no peak
        ({"peak_bytes_in_use": 7, "bytes_limit": 0}, None),  # no limit
        (None, None),  # what a CPU device reports
        (RuntimeError("unimplemented"), None),
    ],
    ids=["tpu", "no_peak", "no_limit", "nothing", "raises"],
)
def test_a_device_s_memory_stats_are_read_as_peak_and_limit(stats, read):
    assert io_preparer._peak_and_limit(_Device(stats)) == read
