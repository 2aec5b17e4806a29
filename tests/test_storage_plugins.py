"""Storage plugin tests (reference analog: tests/test_fs_storage_plugin.py)."""

import asyncio
import io
import os

import pytest

from torchsnapshot_tpu.io_types import IOReq, io_payload
from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin
from torchsnapshot_tpu.storage_plugins.fs import FSStoragePlugin
from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin


def _roundtrip(plugin, path, payload, byte_range=None):
    async def _run():
        await plugin.write(IOReq(path=path, data=payload))
        io_req = IOReq(path=path, byte_range=byte_range)
        await plugin.read(io_req)
        return bytes(io_payload(io_req))

    return asyncio.run(_run())


def test_fs_write_read_delete(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))
    payload = os.urandom(1024)
    assert _roundtrip(plugin, "a/b/c", payload) == payload
    assert (tmp_path / "a" / "b" / "c").exists()
    asyncio.run(plugin.delete("a/b/c"))
    assert not (tmp_path / "a" / "b" / "c").exists()
    plugin.close()


def test_fs_ranged_read(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))
    payload = bytes(range(256))
    assert _roundtrip(plugin, "obj", payload, byte_range=(10, 20)) == payload[10:20]


def test_fs_bytesio_write_path(tmp_path):
    plugin = FSStoragePlugin(root=str(tmp_path))

    async def _run():
        io_req = IOReq(path="x", buf=io.BytesIO(b"hello"))
        await plugin.write(io_req)
        out = IOReq(path="x")
        await plugin.read(out)
        return bytes(io_payload(out))

    assert asyncio.run(_run()) == b"hello"


def test_fs_no_partial_write_visible(tmp_path):
    # Writes go to a temp file then rename: the final name either doesn't
    # exist or holds the full payload.
    plugin = FSStoragePlugin(root=str(tmp_path))
    payload = os.urandom(4096)
    _roundtrip(plugin, "atomic", payload)
    leftovers = [p for p in os.listdir(tmp_path) if p.startswith("atomic.tmp")]
    assert leftovers == []


def test_fs_dir_fsyncs_batch_to_publish_point(tmp_path, monkeypatch):
    # Data-object writes defer their directory fsync; the next publish
    # point (dot-prefixed metadata/marker write) pays one fsync per
    # dirty directory, covering every object renamed into it since.
    from torchsnapshot_tpu.storage_plugins import fs as fs_mod

    synced = []
    monkeypatch.setattr(fs_mod, "_fsync_dir", synced.append)
    plugin = FSStoragePlugin(root=str(tmp_path))
    for i in range(3):
        asyncio.run(plugin.write(IOReq(path=f"shard/obj{i}", data=b"x")))
    # Only the one dir-creation fsync (shard's parent, via _prepare_dir);
    # the three object dirents are deferred — nothing references them yet.
    assert synced == [str(tmp_path)]
    assert plugin._dirty_dirs == {str(tmp_path / "shard")}

    asyncio.run(plugin.write(IOReq(path=".snapshot_metadata", data=b"m")))
    # One batched fsync for the dirty data dir, then one for the dir the
    # metadata itself landed in — in that order.
    assert synced[1:] == [str(tmp_path / "shard"), str(tmp_path)]
    assert plugin._dirty_dirs == set()

    # ensure_durable() — the commit-protocol hook for ranks whose route
    # writes no marker of their own — drains the batch too, including
    # through the retry decorator url_to_storage_plugin wraps with.
    wrapped = url_to_storage_plugin(str(tmp_path))
    wrapped._inner._dirty_dirs.add(str(tmp_path / "shard"))
    wrapped.ensure_durable()
    assert synced[-1] == str(tmp_path / "shard")
    assert wrapped._inner._dirty_dirs == set()

    # close() drains anything a publish never covered.
    asyncio.run(plugin.write(IOReq(path="shard/late", data=b"x")))
    plugin.close()
    assert synced[-1] == str(tmp_path / "shard")


def test_fs_concurrent_writes_keep_the_durability_order(tmp_path, monkeypatch):
    # More data-object writes in flight at once than any caller admits
    # (the scheduler holds to max_write_concurrency; delete, copy and
    # other callers of the plugin do not go through it), into one
    # directory, then a publish point. Each object:
    # tmp -> fsync -> rename, the file's own fsync between the two hooks
    # on the writing thread; the directory's dirents are fsynced after
    # the last data rename and before the publishing rename.
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from torchsnapshot_tpu.io_types import (
        add_storage_op_hook,
        remove_storage_op_hook,
    )
    from torchsnapshot_tpu.storage_plugins import fs as fs_mod

    n = max(8, FSStoragePlugin.max_write_concurrency)
    data_paths = [f"shard/obj{i}" for i in range(n)]
    events = []  # (what, path or directory, thread): appends are atomic
    all_writing = threading.Barrier(n, timeout=60)

    def hook(op, path):
        events.append((op, path, threading.get_ident()))
        if op == "fs.write.tmp" and path in data_paths:
            all_writing.wait()  # every stream is inside its write at once

    real_fsync, real_fsync_dir = os.fsync, fs_mod._fsync_dir

    def fsync(fd):
        real_fsync(fd)
        events.append(("os.fsync", None, threading.get_ident()))

    def fsync_dir(path):
        real_fsync_dir(path)
        events.append(("dirents", path, threading.get_ident()))

    monkeypatch.setattr(fs_mod.os, "fsync", fsync)
    monkeypatch.setattr(fs_mod, "_fsync_dir", fsync_dir)
    plugin = FSStoragePlugin(root=str(tmp_path))

    async def _run():
        # The loop's own default pool is min(32, cores + 4) threads.
        asyncio.get_running_loop().set_default_executor(ThreadPoolExecutor(n))
        await asyncio.gather(
            *(
                plugin.write(IOReq(path=p, data=p.encode() * 1000))
                for p in data_paths
            )
        )
        await plugin.write(IOReq(path=".snapshot_metadata", data=b"m"))

    add_storage_op_hook(hook)
    try:
        asyncio.run(_run())
    finally:
        remove_storage_op_hook(hook)

    def index(what, path):
        (i,) = [i for i, e in enumerate(events) if e[:2] == (what, path)]
        return i

    for p in data_paths + [".snapshot_metadata"]:
        tmp, fsync_at, rename = (
            index(f"fs.write.{step}", p) for step in ("tmp", "fsync", "rename")
        )
        assert tmp < fsync_at < rename
        thread = events[fsync_at][2]
        assert ("os.fsync", None, thread) in events[fsync_at:rename]
        with open(tmp_path / p, "rb") as f:
            assert f.read() == (b"m" if p.startswith(".") else p.encode() * 1000)
    shard_dirents = index("dirents", str(tmp_path / "shard"))
    assert max(index("fs.write.rename", p) for p in data_paths) < shard_dirents
    assert shard_dirents < index("fs.write.rename", ".snapshot_metadata")
    assert plugin._dirty_dirs == set()
    assert not [name for name in os.listdir(tmp_path / "shard") if ".tmp" in name]


def test_fs_fsyncs_created_root_ancestors(tmp_path, monkeypatch):
    # A root that does not exist yet (step dirs under a fresh job dir):
    # makedirs conjures the whole chain, and every created directory's
    # dirent — including the root's own, above the plugin root — must be
    # fsynced, or a crash can drop the entire snapshot directory.
    from torchsnapshot_tpu.storage_plugins import fs as fs_mod

    synced = []
    monkeypatch.setattr(fs_mod, "_fsync_dir", synced.append)
    root = tmp_path / "job" / "step-1"
    plugin = FSStoragePlugin(root=str(root))
    asyncio.run(plugin.write(IOReq(path="shard/obj", data=b"x")))
    # job, step-1, and shard were created: each one's parent is fsynced,
    # top-downward.
    assert synced == [str(tmp_path), str(tmp_path / "job"), str(root)]


def test_memory_plugin():
    plugin = MemoryStoragePlugin()
    payload = os.urandom(64)
    assert _roundtrip(plugin, "k", payload) == payload
    assert _roundtrip(plugin, "k", payload, byte_range=(8, 16)) == payload[8:16]
    asyncio.run(plugin.delete("k"))
    assert "k" not in plugin.store


def test_memory_shared_store():
    a = url_to_storage_plugin("memory://bucket1")
    b = url_to_storage_plugin("memory://bucket1")
    asyncio.run(a.write(IOReq(path="k", data=b"v")))
    io_req = IOReq(path="k")
    asyncio.run(b.read(io_req))
    assert bytes(io_payload(io_req)) == b"v"


def test_url_dispatch(tmp_path):
    # Every resolved plugin is a StoragePlugin wrapped with the retry
    # decorator; the backend type is visible on ._inner.
    from torchsnapshot_tpu.io_types import StoragePlugin

    for url, backend_cls in (
        (str(tmp_path), FSStoragePlugin),
        (f"fs://{tmp_path}", FSStoragePlugin),
        ("memory://x", MemoryStoragePlugin),
    ):
        plugin = url_to_storage_plugin(url)
        assert isinstance(plugin, StoragePlugin)
        assert isinstance(plugin._inner, backend_cls)
    with pytest.raises(RuntimeError, match="Unsupported protocol"):
        url_to_storage_plugin("bogus://x")


def test_installed_plugin_load_error_propagates(monkeypatch):
    # A matched entry point whose load() raises must surface the real
    # error (e.g. a missing optional dep), not "Unsupported protocol" —
    # the plugin IS installed, and the user should be told what broke.
    from torchsnapshot_tpu import storage_plugin as sp_mod

    class BrokenEP:
        name = "myplug"

        def load(self):
            raise ImportError("myplug needs google-cloud-storage")

    class EPs:
        def select(self, group):
            return [BrokenEP()] if group == "storage_plugins" else []

    monkeypatch.setattr(sp_mod.importlib_metadata, "entry_points", EPs)
    with pytest.raises(ImportError, match="google-cloud-storage"):
        url_to_storage_plugin("myplug://bucket")


def test_memory_object_age_visible_across_instances():
    """mtimes ride the SHARED store, not the plugin instance: sweep
    resolves a fresh plugin for the same bucket and its age guard must
    see the ages of objects other instances wrote (code-review r3)."""
    import asyncio

    from torchsnapshot_tpu.io_types import IOReq
    from torchsnapshot_tpu.storage_plugins.memory import MemoryStoragePlugin

    shared = {}
    writer = MemoryStoragePlugin(shared)
    asyncio.run(writer.write(IOReq(path="x", data=b"123")))
    reader = MemoryStoragePlugin(shared)
    age = asyncio.run(reader.object_age_s("x"))
    assert age is not None and age < 60.0
    assert asyncio.run(reader.object_age_s("missing")) is None
