"""Local-filesystem storage plugin.

TPU-native analog of reference torchsnapshot/storage_plugins/fs.py:19-45.
Uses ``asyncio.to_thread``-style executor offloading (via
``loop.run_in_executor``) instead of aiofiles so large writes release the
GIL in one ``file.write`` call; parent-directory creation is cached
(reference fs.py:22,27-30). Supports ranged reads for partial chunk
fetches during resharding.
"""

import asyncio
import errno
import os
import threading
import time
from typing import Any, Optional, Set, Tuple

from .. import telemetry, tracing
from ..io_types import IOReq, StoragePlugin, emit_storage_op


def _payload_nbytes(io_req: IOReq) -> int:
    if io_req.data is not None:
        return len(io_req.data)
    return io_req.buf.getbuffer().nbytes


def _read_into(f: Any, dest: memoryview) -> memoryview:
    """Fill ``dest`` from ``f``; the view of what was read, shorter than
    ``dest`` only where the file ended first (the consumer's length check
    then names the object truncated)."""
    got = 0
    while got < len(dest):
        n = f.readinto(dest[got:])
        if not n:
            break
        got += n
    return dest[:got]


def _read_whole_into(f: Any, dest: memoryview) -> Any:
    """A whole object into ``dest``, which is of the size its entry
    gives: the view of what was read, shorter where the file ended
    first; where the file holds more, all of it in a new ``bytes``. The
    consumer's length check names the object either way."""
    got = _read_into(f, dest)
    if len(got) < len(dest):
        return got
    rest = f.read()
    return bytes(got) + rest if rest else got


def _fsync_dir(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    except OSError as e:
        # Some filesystems (FUSE, 9p, network mounts) reject fsync on a
        # directory fd; degrade to rename-only semantics there rather
        # than failing a write whose data is already durable.
        if e.errno not in (errno.EINVAL, errno.ENOTSUP):
            raise
    finally:
        os.close(fd)


class FSStoragePlugin(StoragePlugin):
    # One durable write stream (tmp -> write -> fsync -> rename) at a
    # time. Measured on the TPU v5e host (PERF.md section 6, PR 26, the
    # sweep of this cap at 1/2/4/8/16): the directory takes 0.78 GB/s
    # from one stream and 0.8-1.2 GB/s from 2 to 16 together, and every
    # stream beyond the first burns kernel CPU against the others,
    # which a training loop on the same host pays in slowed steps (a
    # step under the drain: 124-135 ms at 1, 189-286 ms at 2, 500-520 ms
    # at 8, against 110 ms free) for a drain that ends about a second
    # sooner.
    max_write_concurrency = 1
    # Four read streams. Measured on the same host (PERF.md section 5,
    # PR 38): what set the read rate was the reader's memory, not the
    # directory. Plain reads of 72 parts of 64 MiB into a new `bytes`
    # each give 1.10-1.17 GB/s at 1, 2 and 4 streams, as PR 30 read; into
    # buffers whose pages were touched before, 1.96-2.31 / 3.42-3.81 /
    # 4.85-5.74 GB/s (into fresh `np.empty` pages 0.84-0.89 at any
    # count). With parts read into the restores' staging pool
    # (`IOReq.into`), the restore of 4.08 GB in gpt3-6.7b.kill_resume
    # takes a median 2.63 s at 1 stream, 2.11-2.22 s at 2 and 1.58 s at
    # 4 (one run of 11-17 cycles a value; 4.11-4.20 s at the parent's
    # 2 streams of fresh `bytes`), with the read budget's high water at
    # 0.34 / 0.47-0.60 / 0.81-1.07 GB, inside the pool's 1 GiB: every
    # part of a process's second and later restores reads into a
    # reused buffer. More streams were not measured; they would pass
    # the pool's capacity.
    max_read_concurrency = 4

    def __init__(self, root: str) -> None:
        self.root = root
        self._dir_cache: Set[str] = set()
        # Directories holding renamed-in data objects whose dirents have
        # not been fsynced yet. Data-object writes only record their
        # directory here; the fsyncs are paid once, at the next publish
        # point (see _write_sync), instead of once per object.
        self._dirty_dirs: Set[str] = set()
        self._dirty_lock = threading.Lock()

    def _prepare_dir(self, path: str) -> None:
        dir_path = os.path.dirname(os.path.join(self.root, path))
        if not dir_path or dir_path in self._dir_cache:
            return
        # Record which ancestors are about to be created BEFORE makedirs —
        # including the root itself and anything above it makedirs will
        # conjure — because afterwards there is no telling created from
        # pre-existing. The new dirents must be durable: a crash could
        # otherwise drop a directory whose (fsynced) files committed
        # metadata already references. Each created dir's parent is
        # fsynced once, top-downward; the cache makes it once per
        # directory lifetime.
        created = []
        d = dir_path
        while d and d not in self._dir_cache and not os.path.isdir(d):
            created.append(d)
            parent = os.path.dirname(d)
            if parent == d:
                break
            d = parent
        os.makedirs(dir_path, exist_ok=True)
        for d in reversed(created):
            _fsync_dir(os.path.dirname(d))
            self._dir_cache.add(d)
        self._dir_cache.add(dir_path)

    @staticmethod
    def _is_publish_point(path: str) -> bool:
        """A write that makes previously written objects *referenced*:
        snapshot metadata, commit/step markers — everything the protocol
        keeps under dot-prefixed names. Data objects never are."""
        first = path.split("/", 1)[0]
        return first.startswith(".") or os.path.basename(path).startswith(".")

    def _flush_dirty_dirs(self) -> None:
        with self._dirty_lock:
            dirty, self._dirty_dirs = self._dirty_dirs, set()
        for d in sorted(dirty):
            _fsync_dir(d)

    def ensure_durable(self) -> None:
        # Commit-protocol hook: ranks whose commit route writes no
        # dot-prefixed marker of their own (the KV manifest-gather path)
        # call this before contributing to the commit collective, so
        # their deferred dirents are durable before rank 0 can publish
        # metadata referencing them.
        self._flush_dirty_dirs()

    @staticmethod
    def _writer_alive(pid_str: str) -> bool:
        """Whether the process that named a ``.tmp<pid>`` file still
        runs ON THIS HOST. EPERM means alive (another user's process);
        an unparseable suffix reads as alive — fail toward keeping."""
        if not (pid_str.isascii() and pid_str.isdigit()):
            return True
        try:
            os.kill(int(pid_str), 0)
        except ProcessLookupError:
            return False
        # EPERM (someone else's live process), OverflowError (a numeric
        # suffix past C long — not a real pid), and friends: keep.
        except Exception:  # snapcheck: disable=swallowed-exception -- fails toward keeping
            return True
        return True

    @classmethod
    def _clean_stale_tmp(cls, full: str, own_tmp: str) -> None:
        """Remove torn ``<name>.tmp<pid>`` siblings a CRASHED process
        left for the object about to be (re)written. Stale means the
        writer pid is dead: a live concurrent writer's in-flight tmp
        (e.g. an offline reconcile adopting the marker an async
        finalize is writing right now) must survive, or its rename
        fails with a non-retryable FileNotFoundError — before this
        cleanup existed, concurrent same-path writers were safe under
        last-rename-wins, and they must stay safe. Pid liveness is a
        same-host test; a shared-fs writer from another host may look
        dead — but then BOTH writers are re-driving the same recovery
        path, and the survivor rewrites the object anyway. Only publish
        points pay this (small directories, and they are the paths
        re-driven after a crash — markers, tombstones, metadata);
        payload debris in step directories is reclaimed by sweeps."""
        d = os.path.dirname(full)
        prefix = os.path.basename(full) + ".tmp"
        own = os.path.basename(own_tmp)
        try:
            names = os.listdir(d)
        except FileNotFoundError:
            return
        for name in names:
            if (
                name.startswith(prefix)
                and name != own
                and not cls._writer_alive(name[len(prefix):])
            ):
                try:
                    os.remove(os.path.join(d, name))
                except FileNotFoundError:
                    pass  # concurrent cleanup won the race: already gone

    def _write_sync(self, io_req: IOReq) -> None:
        self._prepare_dir(io_req.path)
        full = os.path.join(self.root, io_req.path)
        publish = self._is_publish_point(io_req.path)
        if publish:
            # Every dirent this marker/metadata may reference must be
            # durable BEFORE the publishing rename can reach disk —
            # writeback gives no ordering on its own.
            self._flush_dirty_dirs()
        # Write to a temp name then rename for per-object atomicity (the
        # reference has no partial-write protection; POSIX rename is free).
        tmp = f"{full}.tmp{os.getpid()}"
        if publish:
            self._clean_stale_tmp(full, tmp)
        payload = io_req.data if io_req.data is not None else io_req.buf.getbuffer()
        # Op-granular boundaries (faultline): a hook may raise here to
        # model a crash BETWEEN the sub-steps of the durability protocol
        # — after the tmp data landed but before it was fsynced, after
        # the fsync but before the rename published it, and after the
        # rename but before the dirent became durable.
        emit_storage_op("fs.write.tmp", io_req.path)
        with open(tmp, "wb") as f:
            f.write(payload)
            emit_storage_op("fs.write.fsync", io_req.path)
            # Data must be durable BEFORE the rename publishes the final
            # name (snapcheck durability-order): a crash shortly after an
            # un-fsynced rename can leave the published name pointing at
            # torn/empty data that the metadata (written later) already
            # references.
            f.flush()
            os.fsync(f.fileno())
        emit_storage_op("fs.write.rename", io_req.path)
        os.replace(tmp, full)
        emit_storage_op("fs.write.dirsync", io_req.path)
        # The rename's dirent must be durable too — immediately for a
        # publish point (it IS the commit), deferred to the next publish
        # point for data objects (nothing references them until then, and
        # one fsync per directory then covers every object in it).
        if publish:
            _fsync_dir(os.path.dirname(full))
        else:
            with self._dirty_lock:
                self._dirty_dirs.add(os.path.dirname(full))

    def _read_sync(
        self, io_req: IOReq, trace_id: Optional[str] = None
    ) -> None:
        full = os.path.join(self.root, io_req.path)
        # Inside the scheduler's ``read`` span, which also holds the
        # wait for a thread of the loop's executor: the open and the
        # read itself, apart, under the trace id of whoever asked.
        with tracing.adopt_trace(trace_id):
            with tracing.span("read.open", path=io_req.path):
                f = open(full, "rb")
            with f, tracing.span("read.io", path=io_req.path):
                if io_req.byte_range is not None:
                    start, end = io_req.byte_range
                    f.seek(start)
                    if io_req.into is not None:
                        payload = _read_into(f, io_req.into())
                    else:
                        payload = f.read(end - start)
                elif io_req.into is not None:
                    payload = _read_whole_into(f, io_req.into())
                else:
                    payload = f.read()
        # Return via `data`: zero-copy for consumers. Callers that want the
        # BytesIO interface read io_req.data themselves (wrapping here
        # would memcpy every payload).
        io_req.data = payload

    async def write(self, io_req: IOReq) -> None:
        loop = asyncio.get_running_loop()
        nbytes = _payload_nbytes(io_req)
        t0 = time.monotonic()
        await loop.run_in_executor(None, self._write_sync, io_req)
        telemetry.record_storage_op(
            "fs", "write", time.monotonic() - t0, nbytes
        )

    async def read(self, io_req: IOReq) -> None:
        loop = asyncio.get_running_loop()
        t0 = time.monotonic()
        await loop.run_in_executor(
            None, self._read_sync, io_req, tracing.current_trace_id()
        )
        telemetry.record_storage_op(
            "fs", "read", time.monotonic() - t0, _payload_nbytes(io_req)
        )

    async def delete(self, path: str) -> None:
        loop = asyncio.get_running_loop()
        t0 = time.monotonic()
        await loop.run_in_executor(None, os.remove, os.path.join(self.root, path))
        telemetry.record_storage_op("fs", "delete", time.monotonic() - t0)

    def _list_sync(self, prefix: str):
        # Object-store semantics: a pure string prefix over relative
        # paths. Walk only the plugin root — never its parent — so a
        # sweep can only ever see this snapshot's own objects (walking
        # dirname(root) for prefix="" would enumerate, and let sweep
        # delete, sibling snapshots). The walk starts at the deepest
        # directory the prefix names: listing ".steps/" over a base
        # holding thousands of payload files must cost O(markers), not
        # O(all objects) — CheckpointManager lists markers on every
        # save/restore.
        found = []
        walk_dir = self.root
        rel_dir = ""
        if "/" in prefix:
            rel_dir = prefix.rsplit("/", 1)[0]
            walk_dir = os.path.join(self.root, rel_dir)
        if not os.path.isdir(walk_dir):
            return found
        for dirpath, _, filenames in os.walk(walk_dir):
            for name in filenames:
                rel = os.path.relpath(os.path.join(dirpath, name), self.root)
                if rel.startswith(prefix):
                    found.append(rel)
        return found

    async def list_prefix(self, prefix: str):
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(None, self._list_sync, prefix)

    async def object_age_s(self, path: str) -> Optional[float]:
        import time

        try:
            st = os.stat(os.path.join(self.root, path))
        except FileNotFoundError:
            return None  # vanished: deleting a missing object is a no-op
        # Other OSErrors (stale NFS handle, perms) propagate: the sweep
        # age guard fails closed on them instead of sweeping blind.
        return max(0.0, time.time() - st.st_mtime)

    async def object_size_bytes(self, path: str) -> Optional[int]:
        try:
            return os.stat(os.path.join(self.root, path)).st_size
        except FileNotFoundError:
            return None

    def close(self) -> None:
        # Belt-and-braces: a plugin retired without ever hitting a
        # publish point (e.g. an aborted take) still leaves every dirent
        # it created durable.
        self._flush_dirty_dirs()
