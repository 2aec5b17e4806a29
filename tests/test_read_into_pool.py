"""A streamed part is read into a buffer of the restores' staging pool
(``IOReq.into``, filled by the fs plug-in's ``readinto``) and the buffer
goes back once the part has landed and been folded: a later restore
reads into pages an earlier one faulted in. Only where the part's put
copies (never where it may alias the buffer) and only through a plug-in
that fills a destination. No test here reads a clock.
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import torchsnapshot_tpu.snapshot as snapshot_mod
from torchsnapshot_tpu import Snapshot, StateDict, staging_pool
from torchsnapshot_tpu.io_preparer import _StreamingSplitState
from torchsnapshot_tpu.io_types import IOReq
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin

_PART = 4096


@pytest.fixture(autouse=True)
def _fresh_pool(monkeypatch):
    monkeypatch.setenv("TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(_PART))
    staging_pool.reset_staging_pool()
    yield
    staging_pool.reset_staging_pool()


@pytest.fixture
def puts_copy(monkeypatch):
    """The CPU device's puts go through the chunked path, whose
    concatenate copies: as a put across a link does."""
    monkeypatch.setenv("TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER", "1")
    monkeypatch.setenv("TPUSNAPSHOT_H2D_CHUNK_BYTES", str(_PART // 4))


def _report(path):
    with open(os.path.join(path, ".report.restore.json")) as f:
        return json.load(f)["ranks"][0]


def _state(seed):
    rng = np.random.default_rng(seed)
    return {
        # 10.5 parts: the last one shorter than the rest.
        "w": jnp.asarray(rng.standard_normal(21 * _PART // 8), jnp.float32),
        "v": jnp.asarray(rng.standard_normal(6 * _PART // 4), jnp.float32),
        "small": jnp.asarray(rng.standard_normal(16), jnp.float32),
    }


_STREAMED_BYTES = (21 * _PART // 8 + 6 * _PART // 4) * 4


def _restore(path, like):
    target = StateDict(**{k: jnp.zeros_like(v) for k, v in like.items()})
    Snapshot(path).restore({"m": target})
    return target


@pytest.mark.parametrize("fanout", [1, 2, 4])
def test_a_second_restore_reads_every_part_into_a_reused_buffer(
    tmp_path, monkeypatch, puts_copy, fanout
):
    monkeypatch.setattr(FSStoragePlugin, "max_read_concurrency", fanout)
    state = _state(fanout)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(**state)})
    pool = staging_pool.get_staging_pool()
    retained = 0
    for restore in range(2):
        target = _restore(path, state)
        for name, value in state.items():
            assert np.asarray(target[name]).tobytes() == np.asarray(value).tobytes()
        report = _report(path)
        hit, miss = report["read_pool_hit_bytes"], report["read_pool_miss_bytes"]
        assert hit + miss == _STREAMED_BYTES
        # Every buffer is back once the restore has returned.
        assert pool.stats()["in_use_bytes"] == 0
        if restore:
            # Each buffer the first restore left is filled again at
            # least once (the second may hold more at once: a miss).
            assert hit >= retained > 0
        else:
            assert miss > 0
            retained = pool.stats()["free_bytes"]


def test_a_short_part_raises_the_truncation_error_and_gives_its_buffer_back(
    tmp_path, monkeypatch, puts_copy
):
    state = _state(5)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(**state)})
    obj = tmp_path / "snap" / "0" / "m" / "w"
    intact = obj.read_bytes()
    obj.write_bytes(intact[: 5 * _PART + 100])
    given_back = []
    real_give_back = _StreamingSplitState._give_back_part

    def spy(self, start):
        given_back.append(start)
        real_give_back(self, start)

    monkeypatch.setattr(_StreamingSplitState, "_give_back_part", spy)
    with pytest.raises(RuntimeError, match="truncated"):
        _restore(path, state)
    # The short part's buffer went back before the error was raised.
    assert any(start >= 5 * _PART for start in given_back)
    # Parts read but never consumed go back when the failed restore is
    # collected; the pool serves the next restore whatever is left.
    obj.write_bytes(intact)
    target = _restore(path, state)
    for name, value in state.items():
        assert np.asarray(target[name]).tobytes() == np.asarray(value).tobytes()


def test_a_put_that_may_alias_its_buffer_gets_no_pooled_destination(tmp_path):
    """On the CPU device a plain put may alias the numpy buffer: a part
    read into a pooled buffer would be overwritten by the next restore
    through the array restored from it."""
    first, second = _state(1), _state(2)
    for name, state in (("a", first), ("b", second)):
        Snapshot.take(str(tmp_path / name), {"m": StateDict(**state)})
    restored = _restore(str(tmp_path / "a"), first)
    kept = {k: np.array(v) for k, v in first.items()}
    _restore(str(tmp_path / "b"), second)
    for name, value in kept.items():
        assert np.asarray(restored[name]).tobytes() == value.tobytes()
    for name in ("a", "b"):
        report = _report(str(tmp_path / name))
        assert report["read_pool_hit_bytes"] == report["read_pool_miss_bytes"] == 0


class _AllocatingFS(FSStoragePlugin):
    """A plug-in that cannot fill a destination (as object stores,
    ``snapserve`` and the hot tier): it allocates every payload."""

    async def read(self, io_req):
        own = IOReq(path=io_req.path, byte_range=io_req.byte_range)
        await super().read(own)
        io_req.data = own.data


def test_a_plugin_that_ignores_the_destination_restores_as_before(
    tmp_path, monkeypatch, puts_copy
):
    monkeypatch.setattr(snapshot_mod, "url_to_storage_plugin", _AllocatingFS)
    state = _state(3)
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"m": StateDict(**state)})
    for _ in range(2):
        target = _restore(path, state)
        for name, value in state.items():
            assert np.asarray(target[name]).tobytes() == np.asarray(value).tobytes()
        report = _report(path)
        assert report["read_pool_hit_bytes"] == report["read_pool_miss_bytes"] == 0
    assert staging_pool.get_staging_pool().stats()["in_use_bytes"] == 0
