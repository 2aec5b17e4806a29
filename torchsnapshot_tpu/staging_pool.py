"""Pooled host staging buffers: the streaming restore pipeline's, and
(a second pool of the same class, below) a take's assembly buffers.

The pre-fastlane restore allocated a fresh host buffer for every
assembly unit — one ``bytearray(nbytes)`` per split whole-object read
(``_SplitObjectReadState``), per content-chunked object
(``_ContentChunksReadState``), and one ``np.empty`` per target region
(``_TargetRegion``) — and dropped it on the floor after one use. At
restore scale that is GiBs of allocate/fault/free churn sitting inside
the consume executors, and every release re-credited the scheduler's
host budget through a callback path that assumed single-use
allocations.

This module replaces those with a process-wide pool of reusable,
exact-size buffers keyed by the restore plan's region/object sizes
(restore plans repeat sizes heavily — all of a model's layers share a
handful of shapes — so exact-size reuse hits). Concurrent restores
share the one pool; attribution stays per-restore because the
``pool_wait`` sub-step is noted into the caller's captured
:class:`~torchsnapshot_tpu.telemetry.consume_profile.PhaseProfile`.

Budget contract (the fastlane accounting fix): a lease carries at most
ONE scheduler budget re-credit, attached via
:meth:`StagingLease.set_budget_release` and fired exactly once when the
buffer actually returns to the pool — never per sub-read, never twice,
whatever mix of executor threads, H2D-engine callbacks, and error paths
races to release it.

The restore's read destinations: a streamed part (``io_preparer.
_StreamingSplitState``), whose bytes go to a device that copies them,
is read straight into a buffer of this pool (``IOReq.into``, filled by
the fs plug-in's ``readinto``), which goes back once the part has
landed and been folded into its object's checksum; so is a whole object
that exactly covers a device region whose puts copy
(``io_preparer._ChunkCopyConsumer``), whose buffer the region adopts
and gives back once its put has landed. A restore then reads into pages
the host has faulted in already, not into a fresh ``bytes`` an object.
Those leases never wait (``acquire(wait=False)``): the read stage's host
budget bounds them. A restore reads its objects by size, largest first,
so a later restore finds its buffers only where the pool kept what each
size held at once: unless ``TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES`` is
set, the cap follows those bytes from a restore's start, summed over the
sizes, up to the restore's host budget (:func:`begin_restore`), and does
not fall. One cap that holds only what a restore holds at one instant
keeps the sizes it read last, which the next restore reads last, so its
first reads of every size miss again. What is kept between restores
goes with :func:`trim_restore_staging_pool`, which a take also calls
where memwatch's forecast predicts an overcommit.

The take side (:func:`get_take_staging_pool`): the host buffers a take
assembles its chunked leaves in (``ArrayBufferStager._stage_phases``
hands them to ``ops/transfer.parallel_device_get`` as ``out``) come from
a second process-wide pool of this class and go back to it when the
write pipeline has seen ``storage.write`` of the object return, so that
every save of a process after its first copies into pages the host has
already faulted in (PERF.md section 5: the first touch of fresh pages
was 62 % of a host-staged capture's thread-seconds). It never waits and
has no knob: its cap is the most bytes a single take of this process
has held leased at once (:meth:`StagingPool.retain_up_to`), so what
stays resident between saves is at most one capture's bytes, which every
such save holds on the host anyway (and a budget's worth where a sync
take is held to a budget). A take that runs while another still drains
gets fresh memory for what is still leased. The free buffers are let go
by :func:`trim_take_staging_pool` (also called when memwatch's forecast
before a write pipeline predicts an overcommit), and with the process.

The takes' pool hands a free buffer out again only while nothing outside
the pool refers to it (:func:`_viewed_elsewhere`): a storage plug-in
that kept the payload past ``write()``, or a writer thread that a
cancelled pipeline left behind, keeps the buffer and the pool forgets
it.

Env knobs (the restore side's; the take side has none):

- ``TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES`` — pool capacity (default
  1 GiB; unset, a restore raises it to what it held at once, above;
  set, it stays as set). Bounds both the retained free set and the
  point past which new acquisitions wait for a release. ``0`` disables
  pooling entirely (callers fall back to plain allocations).
- ``TPUSNAPSHOT_RESTORE_POOL_WAIT_S`` — max seconds an acquisition
  waits at capacity before allocating past the cap anyway (default 5).
  The cap is a pressure valve, not a correctness limit: the scheduler's
  host-memory budget is the real bound, so the pool must never deadlock
  a pipeline the budget already admitted.
"""

import collections
import os
import sys
import threading
import time
from typing import Any, Callable, Deque, Dict, List, Optional

import numpy as np

from . import telemetry
from .telemetry import consume_profile as _cprof
from .telemetry import memwatch
from .telemetry import metrics as _metric_names
from .utils.env import env_float, env_int

_POOL_BYTES_ENV_VAR = "TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES"
_DEFAULT_POOL_BYTES = 1 << 30
_POOL_WAIT_ENV_VAR = "TPUSNAPSHOT_RESTORE_POOL_WAIT_S"
_DEFAULT_POOL_WAIT_S = 5.0
# snapmem domain names of the two pools.
_RESTORE_DOMAIN = "staging_pool"
_TAKE_DOMAIN = "take_staging_pool"


def pool_capacity_bytes() -> int:
    return env_int(_POOL_BYTES_ENV_VAR, _DEFAULT_POOL_BYTES)


# Leases the garbage collector found unreleased. ``__del__`` can run on
# any thread at any allocation, also inside a critical section of that
# same thread (the metrics registry's, this pool's), so it takes no
# lock: it parks the lease here (a deque append is atomic under the
# GIL) and the next ordinary entry into a pool releases it.
_dropped: Deque["StagingLease"] = collections.deque()


# What ``sys.getrefcount`` reads for a buffer that only a free list
# holds, calibrated with the expression ``_viewed_elsewhere`` uses. A
# memoryview, and every array made by ``np.frombuffer`` or sliced from
# one, holds a reference to the buffer object under it for as long as
# it lives, so a higher count means somebody can still read the bytes.
_calibration_bucket = [bytearray(1)]
_FREE_LIST_REFS = sys.getrefcount(_calibration_bucket[0])


def _viewed_elsewhere(bucket: List[Any], i: int) -> bool:
    return sys.getrefcount(bucket[i]) > _FREE_LIST_REFS


def _release_dropped() -> None:
    """Release the leases ``__del__`` parked. Called at the ordinary
    entries (acquire / give-back / stats / pool reset) before any pool
    lock is taken, never from the collector. The batch is taken out
    first, so the give-back of each finds the queue empty and does not
    nest one level per lease."""
    batch: List[StagingLease] = []
    while _dropped:
        try:
            batch.append(_dropped.popleft())
        except IndexError:  # another thread drained it first
            break
    for lease in batch:
        lease.release()


class StagingLease:
    """One pooled buffer, owned by exactly one consumer state at a time.

    ``release()`` is idempotent: the first call returns the buffer to
    the pool and fires the attached scheduler-budget re-credit (if any)
    exactly once; later calls are no-ops. Error paths can therefore
    release defensively without double-crediting the budget.
    """

    __slots__ = ("buffer", "nbytes", "reused", "_pool", "_released",
                 "_budget_cb", "_budget_nbytes", "_lock")

    def __init__(
        self,
        pool: "StagingPool",
        buffer: Any,  # a bytearray; of the takes' pool a uint8 array
        nbytes: int,
        reused: bool = False,
    ):
        self.buffer = buffer
        self.nbytes = nbytes
        # Whether the pool had the buffer (its pages touched by an
        # earlier user) or allocated it for this lease.
        self.reused = reused
        self._pool = pool
        self._released = False
        self._budget_cb: Optional[Callable[[int], None]] = None
        self._budget_nbytes = 0
        self._lock = threading.Lock()

    def set_budget_release(
        self, cb: Callable[[int], None], nbytes: int
    ) -> None:
        """Attach the scheduler's budget re-credit for this buffer's
        reservation. Fired once, at actual release — the pooled analog
        of the single-use releaser callback, minus the assumption that
        every allocation dies with its consume."""
        fire = False
        with self._lock:
            if self._released:
                fire = True  # raced a release: credit now, once
            else:
                self._budget_cb = cb
                self._budget_nbytes = nbytes
        if fire:
            cb(nbytes)

    def as_array(self, dtype: np.dtype, shape: List[int]) -> np.ndarray:
        count = 1
        for s in shape:
            count *= s
        return np.frombuffer(
            self.buffer, dtype=dtype, count=count
        ).reshape(shape)

    def release(self) -> None:
        with self._lock:
            if self._released:
                return
            self._released = True
            cb, self._budget_cb = self._budget_cb, None
            nbytes = self._budget_nbytes
            buffer = self.buffer
            if self._pool.take_side:
                # A reference from a released lease would read as a
                # view of the buffer.
                self.buffer = None
        if cb is not None:
            cb(nbytes)
        self._pool._give_back(buffer, self.nbytes)

    def __del__(self) -> None:
        # Safety net for error paths (a failed restore dropping its
        # plan mid-flight): an unreachable lease can have no live views
        # into its buffer from the pipeline that owned it, so returning
        # it keeps the pool's in-use accounting honest across repeated
        # failure injections (faultline crash matrices). Nobody else can
        # see an unreachable lease, so the flag is read without the
        # lock; the release itself happens in _release_dropped().
        # (At interpreter exit the module's names may be gone already.)
        if not self._released and _dropped is not None:
            _dropped.append(self)


class StagingPool:
    """Exact-size-bucketed free lists with a byte cap and bounded waits."""

    def __init__(
        self,
        capacity_bytes: int,
        max_wait_s: Optional[float] = None,
        take_side: bool = False,
    ) -> None:
        self.capacity_bytes = capacity_bytes
        # The takes' pool differs in what its users differ in. A take's
        # payload goes to a storage plug-in, somebody else's code, where
        # the restore's consumers own every view of their buffers and
        # drop them before the release: so a free buffer that something
        # outside this pool still refers to leaves it instead of being
        # handed out. The ``tpusnapshot_restore_staging_pool_*`` metrics
        # are the restore pool's alone; the takes' pool shows in its
        # snapmem domain and in each take's report (``stage_phases``).
        # In both, a miss allocates untouched pages (``np.empty``),
        # which whoever fills the buffer faults in outside the pool's
        # lock: sixteen copying threads side by side in a take, a
        # plug-in's ``readinto`` in a restore.
        self.take_side = take_side
        self.max_wait_s = (
            max_wait_s
            if max_wait_s is not None
            else env_float(_POOL_WAIT_ENV_VAR, _DEFAULT_POOL_WAIT_S)
        )
        self._cond = threading.Condition()
        self._free: Dict[int, List[bytearray]] = {}
        self._free_bytes = 0
        self._in_use_bytes = 0
        self._high_water_bytes = 0
        # By size: the leases out now, and the most out at once since
        # the last ``retain_held`` (with their bytes summed), up to
        # whose bound the cap follows those bytes.
        self._leased: Dict[int, int] = {}
        self._held: Dict[int, int] = {}
        self._held_bytes = 0
        self._retain_bound = 0
        # Whether the cap was set by the user (a restore never raises it).
        self.cap_is_set = False
        # snapmem: retained + leased bytes against the pool cap. Leased
        # bytes are pinned (a live restore holds them); retained free
        # buffers are evictable by design. Residual tracking watches
        # the pinned side — free buffers are retention, leaked LEASES
        # are the drift the sentinel must name.
        self._mem_domain = memwatch.register(
            _TAKE_DOMAIN if take_side else _RESTORE_DOMAIN,
            cap_bytes=capacity_bytes,
            watch_residual="pinned",
            owner=self,
        )

    # ------------------------------------------------------------ acquire
    def acquire(
        self,
        nbytes: int,
        profile: Optional["_cprof.PhaseProfile"] = None,
        wait: bool = True,
    ) -> StagingLease:
        """A buffer of exactly ``nbytes``, reused when the pool holds
        one. At capacity (outstanding + request past the cap while
        other leases are live) the call waits — bounded by
        ``max_wait_s`` — for a release, noting the wait into
        ``profile`` as the ``pool_wait`` sub-step; it then allocates
        past the cap rather than ever deadlocking the pipeline.
        ``wait=False`` never waits: for a caller whose leases another
        budget already bounds (a restore's read destinations, held to
        the read stage's host budget)."""
        _release_dropped()
        with self._cond:
            if not self.take_side:
                self._note_lease_locked(nbytes)
            buf = self._take_free_locked(nbytes)
            if buf is None and not self.take_side:
                # No exact-size hit: retained free buffers of OTHER
                # sizes are just idle bytearrays — evict them to make
                # capacity room rather than stalling behind them (a
                # cap full of model A's region sizes must not make
                # model B's restore wait out max_wait_s per buffer).
                # (The takes' cap bounds what is retained, not a take
                # in flight: it makes room when a buffer comes back.)
                self._evict_free_locked(nbytes)
            if (
                buf is None
                and wait
                and self.max_wait_s > 0
                and self._must_wait_locked(nbytes)
            ):
                with _cprof.substep(profile, "pool_wait", nbytes):
                    deadline = time.monotonic() + self.max_wait_s
                    while buf is None and self._must_wait_locked(nbytes):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cond.wait(remaining)
                        buf = self._take_free_locked(nbytes)
                self._count("waits", _metric_names.RESTORE_POOL_WAITS)
                if buf is None:
                    buf = self._take_free_locked(nbytes)
            reused = buf is not None
            if reused:
                self._count("hits", _metric_names.RESTORE_POOL_HITS)
            else:
                try:
                    buf = np.empty(nbytes, np.uint8)
                except BaseException:
                    if not self.take_side:
                        self._unnote_lease_locked(nbytes)
                    raise
                self._count("misses", _metric_names.RESTORE_POOL_MISSES)
            self._in_use_bytes += nbytes
            self._publish_locked()
        return StagingLease(self, buf, nbytes, reused)

    def _note_lease_locked(self, nbytes: int) -> None:
        """Count a lease of ``nbytes`` out (the restores' pool), and
        raise the cap (up to the bound ``retain_held`` set) to what the
        leases of each size held at once: before a miss makes room,
        which then takes none of what the sizes held at once keep."""
        out = self._leased.get(nbytes, 0) + 1
        self._leased[nbytes] = out
        if out > self._held.get(nbytes, 0):
            self._held[nbytes] = out
            self._held_bytes += nbytes
            keep = min(self._held_bytes, self._retain_bound)
            if keep > self.capacity_bytes:
                self.capacity_bytes = keep
                self._mem_domain.set_cap(keep)

    def _unnote_lease_locked(self, nbytes: int) -> None:
        out = self._leased.get(nbytes, 0) - 1
        if out > 0:
            self._leased[nbytes] = out
        else:
            self._leased.pop(nbytes, None)

    def retain_held(self, bound: int) -> None:
        """From now on keep, up to ``bound`` bytes, the buffers of each
        size that are leased at once from this call on (see
        :func:`begin_restore`): the cap rises to those bytes, summed
        over the sizes, so that a restore like this one finds every
        buffer it holds at once. Counted per size and not at one
        instant, since a restore reads each Stateful's objects by size,
        largest first: the buffers of the sizes it read first are what
        the next one reads first. The cap never falls."""
        with self._cond:
            self._held = dict(self._leased)
            self._held_bytes = sum(
                size * out for size, out in self._held.items()
            )
            self._retain_bound = bound

    def _count(self, event: str, restore_metric: str) -> None:
        self._mem_domain.counter(event)
        if not self.take_side:
            telemetry.counter(restore_metric).inc(1)

    def _take_free_locked(self, nbytes: int) -> Optional[Any]:
        """A free buffer of ``nbytes``. One of the takes' pool that
        something outside the pool refers to (a plug-in kept the
        payload, a cancelled pipeline's writer thread still reads it)
        leaves the pool here instead: whoever views it owns it."""
        bucket = self._free.get(nbytes)
        buf = None
        while bucket and buf is None:
            viewed = self.take_side and _viewed_elsewhere(
                bucket, len(bucket) - 1
            )
            candidate = bucket.pop()
            self._free_bytes -= nbytes
            if not viewed:
                buf = candidate
        if bucket is not None and not bucket:
            del self._free[nbytes]
        return buf

    def _evict_free_locked(self, need_bytes: int) -> None:
        """Drop retained free buffers until ``need_bytes`` fits inside
        the cap alongside the current outstanding bytes (or the free
        set is empty). Eviction is cheap — the buffers are plain
        bytearrays nobody references. When live leases alone already
        exceed the cap, eviction cannot help: keep the cache (those
        buffers are exactly what the in-flight restores will re-acquire
        next) and let the caller's bounded wait handle it."""
        if self._in_use_bytes + need_bytes > self.capacity_bytes:
            return
        while (
            self._free_bytes > 0
            and self._in_use_bytes + self._free_bytes + need_bytes
            > self.capacity_bytes
        ):
            self._drop_oldest_free_locked()

    def _drop_oldest_free_locked(self) -> None:
        size = next(iter(self._free))
        bucket = self._free[size]
        bucket.pop()
        if not bucket:
            del self._free[size]
        self._free_bytes -= size

    def _must_wait_locked(self, nbytes: int) -> bool:
        # Free bytes are evictable (see acquire) — only bytes held by
        # LIVE leases can force a wait for a release.
        return (
            self._in_use_bytes > 0
            and self._in_use_bytes + nbytes > self.capacity_bytes
        )

    # ------------------------------------------------------------ release
    def _give_back(self, buffer: bytearray, nbytes: int) -> None:
        _release_dropped()
        with self._cond:
            self._in_use_bytes -= nbytes
            if not self.take_side:
                self._unnote_lease_locked(nbytes)
            if self.take_side:
                # The save that just wrote this buffer is what the next
                # one will look like: the sizes unused for longest (the
                # front of the dict) make room for it.
                while (
                    self._free
                    and self._free_bytes + nbytes > self.capacity_bytes
                ):
                    self._drop_oldest_free_locked()
            if self._free_bytes + nbytes <= self.capacity_bytes:
                self._free.setdefault(nbytes, []).append(buffer)
                self._free_bytes += nbytes
            self._publish_locked()
            self._cond.notify_all()

    def retain_up_to(self, nbytes: int) -> None:
        """Raise the cap to ``nbytes`` if it is lower (the take side:
        the bytes one take holds leased at this moment)."""
        with self._cond:
            if nbytes > self.capacity_bytes:
                self.capacity_bytes = nbytes
                self._mem_domain.set_cap(nbytes)

    def trim(self) -> int:
        """Let every free buffer go; returns the bytes let go. Leased
        buffers are their holders'."""
        _release_dropped()
        with self._cond:
            freed, self._free_bytes = self._free_bytes, 0
            self._free.clear()
            self._publish_locked()
        return freed

    def _publish_locked(self) -> None:
        """Mirror occupancy into the gauges and the snapmem domain
        (retained+leased vs cap, leases pinned). Called with the pool
        condition held after every byte-moving transition."""
        total = self._free_bytes + self._in_use_bytes
        self._high_water_bytes = max(self._high_water_bytes, total)
        if not self.take_side:
            telemetry.gauge(_metric_names.RESTORE_POOL_RETAINED).set(
                float(self._free_bytes)
            )
            telemetry.gauge(_metric_names.RESTORE_POOL_LEASED).set(
                float(self._in_use_bytes)
            )
            telemetry.gauge(_metric_names.RESTORE_POOL_HWM).set(
                float(self._high_water_bytes)
            )
        self._mem_domain.set_used(total, pinned_bytes=self._in_use_bytes)

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, int]:
        _release_dropped()
        with self._cond:
            return {
                "free_bytes": self._free_bytes,
                "in_use_bytes": self._in_use_bytes,
                "capacity_bytes": self.capacity_bytes,
                "high_water_bytes": self._high_water_bytes,
            }


_pool_lock = threading.Lock()
_pool: List[Optional[StagingPool]] = []


def get_staging_pool() -> Optional[StagingPool]:
    """The process-wide pool, or None when pooling is disabled
    (``TPUSNAPSHOT_RESTORE_STAGING_POOL_BYTES=0``). The capacity env is
    read once per process; tests use :func:`reset_staging_pool`."""
    with _pool_lock:
        if not _pool:
            cap = pool_capacity_bytes()
            pool = StagingPool(cap) if cap > 0 else None
            if pool is not None:
                pool.cap_is_set = bool(os.environ.get(_POOL_BYTES_ENV_VAR))
            _pool.append(pool)
        return _pool[0]


def begin_restore(bound: int) -> None:
    """A restore begins: unless the user set its cap, the restores' pool
    keeps, up to ``bound`` (the restore's host budget), what each buffer
    size of this restore holds at once (:meth:`StagingPool.retain_held`)."""
    pool = get_staging_pool()
    if pool is not None and not pool.cap_is_set:
        pool.retain_held(bound)


def trim_restore_staging_pool() -> int:
    """Give back the host memory the restores' pool keeps between
    restores (what one restore held at once, or the cap the user set);
    the next restore reads into fresh pages again. Returns the bytes
    let go."""
    pool = get_staging_pool()
    return pool.trim() if pool is not None else 0


def reset_staging_pool() -> None:
    """Drop the memoized pool (tests re-read the env knobs)."""
    _release_dropped()
    with _pool_lock:
        for pool in _pool:
            if pool is not None:
                pool._mem_domain.close()
        _pool.clear()


_take_pool: List[StagingPool] = []


def get_take_staging_pool() -> StagingPool:
    """The process-wide pool of the takes' assembly buffers (module
    docstring, "The take side"). Starts with a cap of 0: it retains
    nothing until a take has leased something."""
    with _pool_lock:
        if not _take_pool:
            _take_pool.append(
                StagingPool(0, max_wait_s=0.0, take_side=True)
            )
        return _take_pool[0]


def trim_take_staging_pool() -> int:
    """Give back the host memory the take side retains between saves
    (at most one capture's bytes); the next host-staged save pays the
    first touch of fresh pages again. Returns the bytes let go."""
    return get_take_staging_pool().trim()


def reset_take_staging_pool() -> None:
    """Drop the takes' pool with its cap (tests)."""
    _release_dropped()
    with _pool_lock:
        for pool in _take_pool:
            pool._mem_domain.close()
        _take_pool.clear()
