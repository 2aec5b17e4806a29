"""A whole object bound for a device-template region is read into a
buffer of the restores' staging pool (``IOReq.into``, filled by the fs
plug-in's ``readinto``), which the region adopts as its buffer with no
copy and gives back once the put that copies it has landed: by the early
put, or by finalize's wait. Everything else reads into memory the plug-in
allocates, counted as ``read_unpooled_bytes``. On the CPU device a put
copies only through the chunked path, which
``TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER`` turns on. No test here reads a
clock.
"""

import json
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import torchsnapshot_tpu.io_preparer as io_preparer
from torchsnapshot_tpu import Snapshot, StateDict, staging_pool
from torchsnapshot_tpu.io_preparer import ArrayRestorePlan, _TargetRegion

# The chunked put's chunk: a region of two chunks or more is put by it.
_CHUNK = 1024


@pytest.fixture(autouse=True)
def _fresh_pool(monkeypatch):
    # Every object here is read whole.
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("TPUSNAPSHOT_H2D_CHUNK_BYTES", str(_CHUNK))
    staging_pool.reset_staging_pool()
    yield
    staging_pool.reset_staging_pool()


@pytest.fixture
def puts_copy(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER", "1")


def _report(path):
    with open(os.path.join(path, ".report.restore.json")) as f:
        return json.load(f)["ranks"][0]


def _counts(path):
    """(hit, miss, unpooled) of the last restore, which add up to the
    bytes it read."""
    report = _report(path)
    counts = (
        report["read_pool_hit_bytes"],
        report["read_pool_miss_bytes"],
        report["read_unpooled_bytes"],
    )
    assert sum(counts) == report["scheduler_ops"]["read"]["bytes"]
    return counts


def _state(seed):
    rng = np.random.default_rng(seed)
    return {
        # Two of one size, one of another: every one of them two chunks
        # or more, so its put copies.
        "a": jnp.asarray(rng.standard_normal(1024), jnp.float32),
        "b": jnp.asarray(rng.standard_normal(1024), jnp.float32),
        "c": jnp.asarray(rng.standard_normal((16, 128)), jnp.float32),
        # Under two chunks: a plain put, which may alias on the CPU.
        "tiny": jnp.asarray(rng.standard_normal(16), jnp.float32),
    }


_POOLED_BYTES = (1024 + 1024 + 16 * 128) * 4
_TINY_BYTES = 16 * 4


def _take(path, state, **kwargs):
    Snapshot.take(path, {"m": StateDict(**state)}, **kwargs)


def _restore(path, like, host=False):
    zeros = np.zeros_like if host else jnp.zeros_like
    target = StateDict(**{k: zeros(v) for k, v in like.items()})
    Snapshot(path).restore({"m": target})
    return target


def _assert_bits(target, state):
    for name, value in state.items():
        assert np.asarray(target[name]).tobytes() == np.asarray(value).tobytes()


def test_a_second_restore_reads_every_whole_object_into_a_reused_buffer_it_adopts(
    tmp_path, monkeypatch, puts_copy
):
    adopted = []
    real_adopt = _TargetRegion.adopt

    def spy_adopt(self, view, lease=None):
        adopted.append((view, lease))
        real_adopt(self, view, lease)

    copied_into = []
    real_ensure = _TargetRegion.ensure_buffer

    def spy_ensure(self, profile=None):
        copied_into.append(self.nbytes)
        return real_ensure(self, profile)

    # Which leases went back through their region (after its put), and
    # which by another path first.
    inside = threading.local()
    real_region_release = _TargetRegion.release_lease

    def spy_region_release(self):
        inside.region = True
        try:
            real_region_release(self)
        finally:
            inside.region = False

    by_region = {}
    real_release = staging_pool.StagingLease.release

    def spy_release(self):
        by_region.setdefault(id(self), getattr(inside, "region", False))
        real_release(self)

    monkeypatch.setattr(_TargetRegion, "adopt", spy_adopt)
    monkeypatch.setattr(_TargetRegion, "ensure_buffer", spy_ensure)
    monkeypatch.setattr(_TargetRegion, "release_lease", spy_region_release)
    monkeypatch.setattr(staging_pool.StagingLease, "release", spy_release)
    state = _state(0)
    path = str(tmp_path / "snap")
    _take(path, state)
    pool = staging_pool.get_staging_pool()
    for restore in range(2):
        adopted.clear()
        _assert_bits(_restore(path, state), state)
        hit, miss, unpooled = _counts(path)
        assert (hit, miss) == ((_POOLED_BYTES, 0) if restore else (0, _POOLED_BYTES))
        assert unpooled == _TINY_BYTES
        # The region's buffer is the lease's: no copy, no second lease.
        pooled = [(v, lease) for v, lease in adopted if lease is not None]
        assert sorted(v.nbytes for v, _ in pooled) == [4096, 4096, 8192]
        for view, lease in pooled:
            assert np.shares_memory(view, np.asarray(lease.buffer))
            assert by_region[id(lease)]
        assert pool.stats()["in_use_bytes"] == 0
    assert copied_into == []


@pytest.mark.parametrize("route", ["early_put", "finalize", "two_devices"])
def test_each_region_gives_its_lease_back_once_its_put_has_landed(
    tmp_path, monkeypatch, puts_copy, route
):
    """A region put by the overlap engine (on the chip, 32 MiB and more)
    gives its lease back when the engine's put has landed; a smaller one,
    or one of several devices, when finalize's batched put has."""
    if route == "finalize":
        # As on the chip below 32 MiB: no early put, while the put that
        # finalize makes still goes through the chunked path.
        monkeypatch.setattr(io_preparer, "h2d_chunk_bytes", lambda: 1 << 30)
    early, at_finalize = [], []
    real_early = ArrayRestorePlan._early_put_done
    real_finalize = ArrayRestorePlan._finalize_jax

    def spy_early(self, region, fut):
        early.append(region._lease is not None)
        real_early(self, region, fut)
        assert region._lease is None

    def spy_finalize(self):
        leased = [r for r in self._regions if r._lease is not None]
        real_finalize(self)
        at_finalize.append(len(leased))
        assert all(r._lease is None for r in leased)

    monkeypatch.setattr(ArrayRestorePlan, "_early_put_done", spy_early)
    monkeypatch.setattr(ArrayRestorePlan, "_finalize_jax", spy_finalize)
    value = jnp.asarray(np.arange(2048, dtype=np.float32))
    path = str(tmp_path / "snap")
    _take(path, {"w": value})
    target = jnp.zeros_like(value)
    if route == "two_devices":
        mesh = Mesh(np.array(jax.devices()[:2]), ("d",))
        target = jax.device_put(target, NamedSharding(mesh, PartitionSpec()))
    restored = StateDict(w=target)
    Snapshot(path).restore({"m": restored})
    assert np.asarray(restored["w"]).tobytes() == np.asarray(value).tobytes()
    assert _counts(path)[1] == value.nbytes
    if route == "early_put":
        assert early == [True] and at_finalize == [0]
    else:
        assert early == [] and at_finalize == [1]
    assert staging_pool.get_staging_pool().stats()["in_use_bytes"] == 0


def test_back_to_back_restores_of_the_same_sizes_never_show_each_others_bytes(
    tmp_path, puts_copy
):
    first, second = _state(1), _state(2)
    for name, state in (("a", first), ("b", second)):
        _take(str(tmp_path / name), state)
    restored = []
    for name, state in (("a", first), ("b", second), ("a", first), ("b", second)):
        restored.append((_restore(str(tmp_path / name), state), state))
        assert _counts(str(tmp_path / name))[0] == (
            _POOLED_BYTES if len(restored) > 1 else 0
        )
    # Every buffer was refilled by a later restore; no restored array
    # shows it.
    for target, state in restored:
        _assert_bits(target, state)


@pytest.mark.parametrize(
    "case", ["host_template", "compressed", "chunk_store", "cpu_alias"]
)
def test_what_no_device_copies_whole_reads_into_plugin_memory(
    tmp_path, monkeypatch, case
):
    if case != "cpu_alias":
        monkeypatch.setenv("TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER", "1")
    state = _state(3)
    path = str(tmp_path / "snap")
    kwargs = {"compression": "zlib"} if case == "compressed" else {}
    if case == "chunk_store":
        monkeypatch.setenv("TPUSNAPSHOT_CHUNK_MIN_BYTES", "1")
        kwargs = {"chunks": True}
    _take(path, state, **kwargs)
    for _ in range(2):
        _assert_bits(_restore(path, state, host=case == "host_template"), state)
        hit, miss, unpooled = _counts(path)
        assert hit == miss == 0 and unpooled > 0
    assert staging_pool.get_staging_pool().stats()["in_use_bytes"] == 0


def test_parts_whole_objects_and_plugin_memory_add_up_to_the_bytes_read(
    tmp_path, monkeypatch, puts_copy
):
    # "c" (8 KiB) is read as two streamed parts, the rest whole.
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", "4096")
    state = _state(4)
    path = str(tmp_path / "snap")
    _take(path, state)
    for restore in range(2):
        _assert_bits(_restore(path, state), state)
        hit, miss, unpooled = _counts(path)
        assert hit + miss == _POOLED_BYTES and unpooled == _TINY_BYTES
        assert miss == (0 if restore else _POOLED_BYTES)


@pytest.mark.parametrize("cap", ["unset", "set"])
def test_a_pool_smaller_than_a_restore_keeps_what_each_size_held_at_once_unless_its_cap_is_set(
    tmp_path, monkeypatch, puts_copy, cap
):
    """Where the user left the cap unset, it rises to the buffers each
    size held at once over a whole restore (two Statefuls, each its own
    read pipeline, of other sizes), so a process's second restore reads
    every object into a reused buffer though the first cap holds one of
    them. A cap the user set stays as set: misses, still bit-exact."""
    if cap == "set":
        monkeypatch.setenv("TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES", "4096")
    else:
        monkeypatch.setattr(staging_pool, "_DEFAULT_POOL_BYTES", 4096)
    staging_pool.reset_staging_pool()
    rng = np.random.default_rng(5)
    states = {
        "m": _state(5),
        "o": {
            "p": jnp.asarray(rng.standard_normal(768), jnp.float32),
            "q": jnp.asarray(rng.standard_normal(1536), jnp.float32),
        },
    }
    pooled = _POOLED_BYTES + (768 + 1536) * 4
    path = str(tmp_path / "snap")
    Snapshot.take(path, {k: StateDict(**v) for k, v in states.items()})
    pool = staging_pool.get_staging_pool()
    for restore in range(3):
        targets = {
            k: StateDict(**{n: jnp.zeros_like(a) for n, a in v.items()})
            for k, v in states.items()
        }
        Snapshot(path).restore(targets)
        for k, v in states.items():
            _assert_bits(targets[k], v)
        hit, miss, _ = _counts(path)
        assert hit + miss == pooled
        if cap == "unset":
            assert (hit, miss) == ((pooled, 0) if restore else (0, pooled))
        else:
            assert miss > 0
    stats = pool.stats()
    assert stats["in_use_bytes"] == 0
    if cap == "unset":
        assert stats["free_bytes"] == stats["capacity_bytes"] >= 8192
        # Let go on request; the cap stays for the next restore.
        assert staging_pool.trim_restore_staging_pool() == stats["free_bytes"]
        assert pool.stats()["free_bytes"] == 0
    else:
        assert stats["capacity_bytes"] == 4096 >= stats["free_bytes"]


@pytest.mark.parametrize("fault", ["truncated", "longer", "flipped"])
def test_a_whole_object_that_is_not_what_was_saved_raises_and_gives_its_lease_back(
    tmp_path, puts_copy, fault
):
    value = jnp.asarray(np.arange(2048, dtype=np.float32))
    path = str(tmp_path / "snap")
    _take(path, {"w": value})
    obj = tmp_path / "snap" / "0" / "m" / "w"
    payload = bytearray(obj.read_bytes())
    if fault == "truncated":
        payload = payload[:5000]
    elif fault == "longer":
        payload += b"\0" * 8
    else:
        payload[4000] ^= 0xFF
    obj.write_bytes(bytes(payload))
    match = "Checksum mismatch" if fault == "flipped" else "0/m/w.*truncated"
    with pytest.raises(RuntimeError, match=match):
        _restore(path, {"w": value})
    assert staging_pool.get_staging_pool().stats()["in_use_bytes"] == 0


def test_the_cap_follows_what_each_size_held_at_once_up_to_its_bound():
    pool = staging_pool.StagingPool(4096)
    pool.retain_held(1 << 20)
    for _ in range(2):
        leases = [pool.acquire(2048) for _ in range(3)]
        leases += [pool.acquire(1024) for _ in range(2)]
        for lease in leases:
            lease.release()
    stats = pool.stats()
    assert stats["capacity_bytes"] == stats["free_bytes"] == 3 * 2048 + 2 * 1024
    # The second round took every buffer the first had left.
    assert stats["high_water_bytes"] == 3 * 2048 + 2 * 1024
    bounded = staging_pool.StagingPool(4096)
    bounded.retain_held(5000)
    for lease in [bounded.acquire(2048) for _ in range(3)]:
        lease.release()
    assert bounded.stats()["capacity_bytes"] == 5000


def test_the_counts_of_what_each_size_held_survive_threads_side_by_side():
    pool = staging_pool.StagingPool(1, max_wait_s=0.0)
    pool.retain_held(1 << 30)
    sizes = [512, 1024, 2048]

    def work(seed):
        rng = np.random.default_rng(seed)
        for _ in range(200):
            leases = [pool.acquire(int(rng.choice(sizes))) for _ in range(3)]
            for lease in leases:
                lease.release()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    stats = pool.stats()
    # A lost update would leave a lease counted out, or the bytes held
    # at once short of what the pool made.
    assert stats["in_use_bytes"] == 0 and pool._leased == {}
    assert pool._held_bytes == stats["free_bytes"] == stats["capacity_bytes"]


def test_a_lease_whose_buffer_could_not_be_made_is_not_counted(monkeypatch):
    pool = staging_pool.StagingPool(4096)
    pool.retain_held(1 << 20)

    def no_memory(*args, **kwargs):
        raise MemoryError

    monkeypatch.setattr(staging_pool.np, "empty", no_memory)
    with pytest.raises(MemoryError):
        pool.acquire(2048)
    monkeypatch.undo()
    assert pool._leased == {}
    pool.acquire(2048).release()
    assert pool._leased == {} and pool.stats()["free_bytes"] == 2048


def test_a_take_predicted_to_overcommit_lets_the_restores_kept_buffers_go(
    tmp_path, monkeypatch, puts_copy
):
    from torchsnapshot_tpu.telemetry import memwatch

    state = _state(6)
    path = str(tmp_path / "snap")
    _take(path, state)
    _restore(path, state)
    assert staging_pool.get_staging_pool().stats()["free_bytes"] > 0
    monkeypatch.setattr(
        memwatch, "forecast", lambda demand, kind="take": {"overcommit": True}
    )
    _take(str(tmp_path / "again"), state)
    assert staging_pool.get_staging_pool().stats()["free_bytes"] == 0
