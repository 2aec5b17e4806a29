"""Memory-budgeted, pipelined execution of write/read requests.

TPU-native analog of reference torchsnapshot/scheduler.py:23-239. Two
two-stage asyncio pipelines overlap device→host staging / serialization
with storage IO under a per-process host-memory budget:

- write: ``stage_buffer`` (HBM→RAM copy + serialize, thread executor)
  → ``storage.write``;
- read: ``storage.read`` → ``consume_buffer`` (deserialize + RAM→HBM).
  The reads are issued by a stage with a thread and a loop of its own
  (``_ReadStage``): the next read starts when a read returns, whatever
  the loop that admits the consumes is busy with.

Budget accounting is symmetric and conservative (the reference *adds*
instead of subtracting the read budget at dispatch, scheduler.py:209,
making its read budget unbounded; and can leave finished staging tasks
un-reaped, scheduler.py:133-135 — both fixed here):

- write: charge ``staging_cost`` at dispatch; on stage completion re-credit
  ``staging_cost − len(buf)``; on write completion re-credit ``len(buf)``.
- read: charge ``consuming_cost`` at dispatch; re-credit it after consume —
  except a consumer's *deferred* portion (a split read's shared assembly
  buffer, which outlives the individual sub-read consumes; a streamed
  part's payload, which the H2D overlap engine holds until its transfer
  lands), which the consumer re-credits through a releaser callback when
  the allocation is actually freed. Pooled staging buffers
  (``staging_pool.py``) bind that releaser to their lease, which fires
  it exactly ONCE when the buffer returns to the pool — the pre-fastlane
  path assumed single-use allocations, and a pooled buffer re-crediting
  per sub-read would multiply-credit the budget. Releases may arrive
  from engine threads after this loop exited; ``_BudgetCell`` is locked
  for exactly that.

At least one request is always in flight regardless of budget so a single
over-budget buffer cannot deadlock the pipeline (reference
scheduler.py:104-117).
"""

import asyncio
import contextvars
import functools
import io
import logging
import os
import socket
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import psutil

from . import staging_pool, telemetry, tracing
from .io_types import IOReq, ReadReq, StoragePlugin, WriteReq, io_payload
from .telemetry import consume_profile as _cprof
from .telemetry import memwatch
from .telemetry import metrics as _metric_names

logger = logging.getLogger(__name__)

_MAX_PER_RANK_MEMORY_BUDGET_BYTES: int = 32 * 1024 * 1024 * 1024
_AVAILABLE_MEMORY_MULTIPLIER: float = 0.8
_MAX_STAGING_THREADS: int = 16

_MEMORY_BUDGET_ENV_VAR = "TPUSNAPSHOT_PER_RANK_MEMORY_BUDGET_BYTES"


def get_local_world_size(coord) -> int:
    """Number of snapshot processes on this host (hostname all-gather).

    Reference analog: scheduler.py:29-38.
    """
    hostnames = coord.all_gather_object(socket.gethostname())
    return max(1, hostnames.count(socket.gethostname()))


def get_process_memory_budget_bytes(coord) -> int:
    """min(0.8 × available RAM ÷ local procs, 32 GB), env-overridable.

    Reference analog: scheduler.py:41-61. Runs a collective (hostname
    all-gather) — only call from paths where every process participates.
    """
    env_val = os.environ.get(_MEMORY_BUDGET_ENV_VAR)
    if env_val is not None:
        budget = int(env_val)
        logger.info("Memory budget overridden by env var: %d bytes", budget)
        return budget
    local_world_size = get_local_world_size(coord)
    return _memory_budget_for_local_world(local_world_size)


def get_local_memory_budget_bytes() -> int:
    """Collective-free budget (assumes this is the host's only snapshot
    process) for single-process operations like ``Snapshot.read_object``."""
    env_val = os.environ.get(_MEMORY_BUDGET_ENV_VAR)
    if env_val is not None:
        return int(env_val)
    return _memory_budget_for_local_world(1)


def _memory_budget_for_local_world(local_world_size: int) -> int:
    available = psutil.virtual_memory().available
    budget = min(
        int(available * _AVAILABLE_MEMORY_MULTIPLIER) // local_world_size,
        _MAX_PER_RANK_MEMORY_BUDGET_BYTES,
    )
    logger.info("Per-process memory budget: %d MB", budget // 1024 // 1024)
    return budget


def _observe_op(
    ops: Dict[str, Dict[str, Any]],
    op: str,
    seconds: float,
    nbytes: int,
    progress: Optional[Any] = None,
    progress_bytes: int = 0,
) -> None:
    """Record one pipelined op in the always-on metrics AND the per-call
    aggregate (the flight recorder's exact per-operation numbers). Only
    ever called from the event-loop thread, so the plain dict is safe.
    ``progress`` (a telemetry ProgressPublisher) gets the same pulse —
    its heartbeat beats exactly as often as the pipeline completes
    work, which is what makes a stale heartbeat mean "stuck".
    ``progress_bytes`` is this op's credit against the announced
    bytes_total — in cost units, NOT stored-payload bytes (``nbytes``),
    which diverge under compression; ops that re-describe payloads a
    sibling op already credited pass 0."""
    telemetry.record_scheduler_op(op, seconds, nbytes)
    agg = ops.setdefault(op, {"count": 0, "seconds": 0.0, "bytes": 0})
    agg["count"] += 1
    agg["seconds"] += seconds
    agg["bytes"] += nbytes
    if progress is not None:
        progress.pipeline_update(op, progress_bytes)


def _merge_stats(
    stats: Optional[Dict[str, Any]],
    pipeline: str,
    nbytes: int,
    stall_s: float,
    high_water: int,
    ops: Dict[str, Dict[str, Any]],
) -> None:
    """Fold one pipeline run's aggregates into the always-on metrics and
    (when the caller wants per-operation attribution) the ``stats``
    accumulator dict."""
    telemetry.counter(
        _metric_names.SCHED_STALL_SECONDS, pipeline=pipeline
    ).inc(stall_s)
    telemetry.gauge(
        _metric_names.SCHED_BUDGET_HWM, pipeline=pipeline
    ).set_max(high_water)
    if stats is None:
        return
    stats["bytes"] = stats.get("bytes", 0) + nbytes
    stats["stall_s"] = stats.get("stall_s", 0.0) + stall_s
    stats["budget_high_water_bytes"] = max(
        stats.get("budget_high_water_bytes", 0), high_water
    )
    out = stats.setdefault("ops", {})
    for op, agg in ops.items():
        acc = out.setdefault(op, {"count": 0, "seconds": 0.0, "bytes": 0})
        acc["count"] += agg["count"]
        acc["seconds"] += agg["seconds"]
        acc["bytes"] += agg["bytes"]


async def execute_write_reqs(
    write_reqs: List[WriteReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    stats: Optional[Dict[str, Any]] = None,
    progress: Optional[Any] = None,
) -> int:
    """Run the staged-write pipeline; returns total bytes written.

    ``stats`` (optional) accumulates this run's exact aggregates —
    bytes, per-op count/seconds/bytes (``write_wait`` among the ops: the
    requests that found every write slot taken), budget stall seconds,
    budget high-water, and the write cap it ran under
    (``write_concurrency``) — for the flight recorder; the same numbers
    also feed the always-on process metrics. ``progress`` (optional
    ProgressPublisher) is pulsed per op completion and cadence-published
    from this loop, so watchers see live bytes/phase while the pipeline
    runs.
    """
    begin_ts = time.monotonic()
    if progress is not None:
        # Pre-staged buffers charge a 0 budget cost but advertise their
        # real size via payload_nbytes — progress totals want bytes to
        # move, not budget to charge.
        progress.add_bytes_total(
            sum(
                getattr(wr.buffer_stager, "payload_nbytes", None)
                or wr.buffer_stager.get_staging_cost_bytes()
                for wr in write_reqs
            )
        )
        # Announce the totals immediately: a pipeline that then blocks
        # on its first storage op still leaves watchers a record with
        # bytes_total (0 done), not a blank.
        await progress.async_tick(force=True)
    pending = deque(write_reqs)
    staged: deque = deque()  # (WriteReq, buf, ready_t)
    staging: Dict[asyncio.Task, Tuple[WriteReq, int]] = {}
    io_tasks: Dict[asyncio.Task, int] = {}
    budget = memory_budget_bytes
    min_budget = memory_budget_bytes
    stall_s = 0.0
    ops: Dict[str, Dict[str, Any]] = {}
    bytes_written = 0
    max_io = storage.max_write_concurrency
    if stats is not None:
        stats["write_concurrency"] = max_io
    # Start of the newest wait on in-flight work: a staged buffer that
    # was ready before it has sat through a wait with every write slot
    # taken (the dispatch below holds a buffer back for nothing else).
    wait_t0 = begin_ts
    executor = ThreadPoolExecutor(max_workers=_MAX_STAGING_THREADS)
    # Live budget gauges (snapscope): occupancy + stalled-right-now, so
    # the runtime sampler can see budget pressure while it happens
    # instead of post-hoc from the stall counter. Reset on exit.
    in_use_gauge = telemetry.gauge(
        _metric_names.SCHED_BUDGET_IN_USE, pipeline="write"
    )
    stalled_gauge = telemetry.gauge(
        _metric_names.SCHED_BUDGET_STALLED, pipeline="write"
    )
    # snapmem: the write budget is transient host RAM — staged buffers
    # live only between stage and write completion, so any residual
    # after the pipeline exits is a leak signal. Pre-storm forecast:
    # the allocation burst is bounded by min(total staging cost,
    # budget) since dispatch throttles at the budget line.
    mem_domain = memwatch.register(
        "scheduler.write",
        cap_bytes=memory_budget_bytes,
        transient=True,
        watch_residual="used",
    )
    if memwatch.forecast(
        min(
            sum(
                wr.buffer_stager.get_staging_cost_bytes()
                for wr in write_reqs
            ),
            memory_budget_bytes,
        ),
        kind="take",
    ):
        # The burst is predicted not to fit: the assembly buffers that
        # earlier takes left in their pool, and the read buffers that
        # earlier restores left in theirs, are what this pipeline can
        # give the host back first.
        staging_pool.trim_take_staging_pool()
        staging_pool.trim_restore_staging_pool()
    try:
        while pending or staged or staging or io_tasks:
            # Dispatch staging while the budget allows; always keep at
            # least one request moving.
            budget_blocked = False
            while pending:
                cost = pending[0].buffer_stager.get_staging_cost_bytes()
                nothing_in_flight = not (staging or staged or io_tasks)
                if budget >= cost or nothing_in_flight:
                    wr = pending.popleft()
                    budget -= cost
                    min_budget = min(min_budget, budget)

                    async def _stage(wr=wr, cost=cost):
                        t0 = time.monotonic()
                        with tracing.span("stage", path=wr.path, bytes=cost):
                            buf = await wr.buffer_stager.stage_buffer(executor)
                        _observe_op(
                            ops,
                            "stage",
                            time.monotonic() - t0,
                            len(buf),
                            progress,
                        )
                        # Codec stage (chunkstore.py ChunkStager): the
                        # encode ran inside the stage above; surface it
                        # as its own op so flight reports separate
                        # "device→host + serialize" from "compress/
                        # quantize" CPU time. Credits no progress bytes
                        # (the stage op already did).
                        enc = getattr(
                            wr.buffer_stager, "encode_stats", None
                        )
                        if enc is not None:
                            _observe_op(ops, "encode", enc[0], enc[1])
                            telemetry.counter(
                                _metric_names.CODEC_SECONDS, op="encode"
                            ).inc(enc[0])
                        return buf

                    task = asyncio.ensure_future(_stage())
                    staging[task] = (wr, cost)
                else:
                    budget_blocked = True
                    break
            # Dispatch storage writes up to the backend's concurrency cap.
            while staged and len(io_tasks) < max_io:
                wr, buf, ready_t = staged.popleft()
                if ready_t < wait_t0:
                    # write_wait (the mirror of the read side's
                    # read_wait): staged buffer ready -> write
                    # dispatched, for the requests the cap held back.
                    _observe_op(
                        ops,
                        "write_wait",
                        time.monotonic() - ready_t,
                        len(buf),
                    )
                io_req = IOReq(path=wr.path, data=buf)
                # Progress credit in the SAME units bytes_total summed
                # (cost / payload_nbytes, pre-compression) — len(buf)
                # is post-compression and would stall the % short.
                share = (
                    getattr(wr.buffer_stager, "payload_nbytes", None)
                    or wr.buffer_stager.get_staging_cost_bytes()
                )

                async def _write(
                    io_req=io_req,
                    path=wr.path,
                    n=len(buf),
                    share=share,
                    stager=wr.buffer_stager,
                ):
                    t0 = time.monotonic()
                    try:
                        with tracing.span("write", path=path, bytes=n):
                            await storage.write(io_req)
                    finally:
                        # ``write`` has returned, raised or been
                        # cancelled: a pooled buffer under the payload
                        # goes back (its pool hands it out again only
                        # once nothing views it, staging_pool.py).
                        io_req.data = None
                        stager.release_staged()
                    _observe_op(
                        ops,
                        "write",
                        time.monotonic() - t0,
                        n,
                        progress,
                        progress_bytes=share,
                    )

                task = asyncio.ensure_future(_write())
                io_tasks[task] = len(buf)

            in_use_gauge.set(memory_budget_bytes - budget)
            mem_domain.set_used(
                max(0, memory_budget_bytes - budget),
                pinned_bytes=max(0, memory_budget_bytes - budget),
            )
            stalled_gauge.set(1.0 if budget_blocked else 0.0)
            in_flight = set(staging) | set(io_tasks)
            if not in_flight:
                continue
            wait_t0 = time.monotonic()
            done, _ = await asyncio.wait(
                in_flight, return_when=asyncio.FIRST_COMPLETED
            )
            if budget_blocked:
                # Work was ready to dispatch but the budget said no: the
                # time until the next completion is budget-wait stall.
                stall_s += time.monotonic() - wait_t0
            for task in done:
                if task in staging:
                    wr, cost = staging.pop(task)
                    buf = task.result()
                    budget += cost - len(buf)
                    staged.append((wr, buf, time.monotonic()))
                else:
                    buf_len = io_tasks.pop(task)
                    task.result()  # propagate storage errors
                    budget += buf_len
                    bytes_written += buf_len
            if progress is not None:
                await progress.async_tick()
    finally:
        executor.shutdown(wait=False)
        # On the way out of a failed or cancelled run: what was staged
        # and never written gives its pooled buffer back too. After a
        # sound run every stager has done so already.
        for wr in write_reqs:
            wr.buffer_stager.release_staged()
        in_use_gauge.set(0)
        stalled_gauge.set(0)
        mem_domain.set_used(max(0, memory_budget_bytes - budget))
        mem_domain.close()
    elapsed = time.monotonic() - begin_ts
    _merge_stats(
        stats,
        "write",
        bytes_written,
        stall_s,
        memory_budget_bytes - min_budget,
        ops,
    )
    mbps = bytes_written / 1024 / 1024 / elapsed if elapsed > 0 else 0.0
    logger.info(
        "Rank %d finished saving (%d bytes). Throughput: %.2f MB/s",
        rank,
        bytes_written,
        mbps,
    )
    return bytes_written


class _BudgetCell:
    """Mutable budget shared with consumers holding deferred reservations
    (split-read assembly buffers, streaming-split crc stashes): ``release``
    re-credits when the backing allocation is actually freed, not when a
    consume task completes. Locked: streaming splits release from executor
    threads as their in-order prefix drains, racing the charges of the
    read stage's thread and the event loop's refunds. The callback of
    ``call_on_release`` runs after every release, outside the lock: the
    read stage's wake-up when the head of its queue waits for room."""

    __slots__ = ("value", "_lock", "_charges", "_releases", "_on_release")

    def __init__(self, value: int) -> None:
        self.value = value
        self._lock = threading.Lock()
        self._charges = 0
        self._releases = 0
        self._on_release: Optional[Callable[[], None]] = None

    def call_on_release(self, callback: Callable[[], None]) -> None:
        with self._lock:
            self._on_release = callback

    def charge(self, nbytes: int) -> None:
        with self._lock:
            self.value -= nbytes
            self._charges += 1

    def release(self, nbytes: int) -> None:
        with self._lock:
            self.value += nbytes
            self._releases += 1
            on_release = self._on_release
        if on_release is not None:
            on_release()

    def charge_count(self) -> int:
        with self._lock:
            return self._charges

    def release_count(self) -> int:
        with self._lock:
            return self._releases


# Force-admission grace: when nothing is in flight on the event loop but
# the head still cannot be admitted under budget, a completed consume's
# deferred release may still be riding an engine/executor thread the OS
# hasn't scheduled (H2D done-callbacks resolve after the consume task
# does). Bound how long the pipeline waits for such a straggler before
# it force-admits and accepts the overrun.
_FORCE_ADMIT_GRACE_S = 0.5
_FORCE_ADMIT_POLL_S = 0.005


async def _straggler_release_landed(cell: _BudgetCell) -> bool:
    """Wait up to the grace window for ANY release on ``cell``; True
    means one landed and the caller should rescan under the refreshed
    budget instead of force-admitting."""
    if cell.charge_count() == 0:
        # Nothing was ever charged, so no release can possibly be in
        # flight — force-admit immediately (the solo over-budget head
        # at t=0 must not pay the grace).
        return False
    baseline = cell.release_count()
    deadline = time.monotonic() + _FORCE_ADMIT_GRACE_S
    while time.monotonic() < deadline:
        await asyncio.sleep(_FORCE_ADMIT_POLL_S)
        if cell.release_count() != baseline:
            return True
    return cell.release_count() != baseline


def _start_threads(executor: ThreadPoolExecutor, count: int) -> None:
    """Have ``executor`` start ``count`` of its threads now.

    A ``ThreadPoolExecutor`` starts a thread inside ``submit`` when none
    is idle, under ``concurrent.futures``' process-wide lock. A restore
    that left that to its first sixteen consumes paid for it while reads
    were in flight: consume threads queued on that lock behind the
    event loop's thread, which was starting threads (PERF.md section 5,
    PR 30). Every submit here finds the earlier tasks still held at the
    gate, so each starts a thread; afterwards all of them are idle."""
    gate = threading.Event()
    held = [executor.submit(gate.wait) for _ in range(count)]
    gate.set()
    for task in held:
        task.result()


# How long a failed or cancelled run waits for the read stage's thread
# to leave its loop. Its reads are cancelled first, so this is reached
# only by a plug-in whose ``read`` does not yield to cancellation.
_READ_STAGE_JOIN_S = 5.0


class _ReadStage:
    """The read half of :func:`execute_read_reqs`, on a thread and an
    event loop of its own.

    It keeps up to ``storage.max_read_concurrency`` calls of
    ``storage.read`` in flight, in the order of ``pending`` and under
    the host budget, and starts the next one when one returns: not when
    the loop that admits the consumes next comes round, which answers an
    event 0.16-0.21 s late while sixteen consumes fold and submit
    (PERF.md section 5, PR 30). A returned payload is posted to that
    loop (``deliver``); so is the first failure (``fail``).

    ``storage.read`` is the only call into the plug-in. Its threads
    (the default executor of the stage's loop, which
    ``loop.run_in_executor(None, ...)`` in a plug-in lands on) exist
    before the first read is issued.

    What the two threads share: the budget cell (locked); ``issued``
    and ``consumed``, each written by one thread alone (the stage
    counts the reads it has charged, the consumes' loop the requests
    whose consume has ended: equal means nothing is in flight anywhere,
    which is when a head above the budget is admitted all the same);
    ``reads_in_flight``, written here and read there.
    """

    def __init__(
        self,
        pending: "deque[ReadReq]",
        storage: StoragePlugin,
        memory_budget_bytes: int,
        deliver: Callable[[ReadReq, Any, int, float], None],
        fail: Callable[[BaseException], None],
    ) -> None:
        self._pending = pending
        self._storage = storage
        # The host budget: charged here as a read is issued, given back
        # by the consumes' loop and by the consumers' deferred
        # releasers, each of which wakes a head that waits for room.
        self.budget = _BudgetCell(memory_budget_bytes)
        self.budget.call_on_release(self.wake)
        self._deliver = deliver
        self._fail = fail
        self.streams = max(1, storage.max_read_concurrency)
        self._consumes_loop = asyncio.get_running_loop()
        self._loop = asyncio.new_event_loop()
        self._executor = ThreadPoolExecutor(
            max_workers=self.streams,
            thread_name_prefix="tpusnapshot-read",
        )
        self._loop.set_default_executor(self._executor)
        self._released = asyncio.Event()
        self._run_task: Optional[asyncio.Task] = None
        # The restore's trace id and phase profile are context
        # variables: the stage's tasks run under a copy of the caller's.
        self._thread = threading.Thread(
            target=contextvars.copy_context().run,
            args=(self._thread_main,),
            name="tpusnapshot-read-stage",
            daemon=True,
        )
        self.issued = 0
        self.consumed = 0
        self.reads_in_flight = 0
        self.budget_blocked = False
        self.stall_s = 0.0
        self.min_budget = memory_budget_bytes
        # Seconds between the first read issued and the last returned
        # with no ``storage.read`` in flight: the report's
        # ``read_idle_s``.
        self.read_idle_s = 0.0
        self._idle_since: Optional[float] = None
        # Where a consumer can take it, a read lands in a buffer of the
        # restores' staging pool (``IOReq.into``): the bytes read into
        # one an earlier read had filled, into a new one and into memory
        # the plug-in allocated are the report's
        # ``read_pool_hit_bytes`` / ``read_pool_miss_bytes`` /
        # ``read_unpooled_bytes``, which add up to the bytes read.
        self._pool = staging_pool.get_staging_pool()
        self.pool_hit_bytes = 0
        self.pool_miss_bytes = 0
        self.unpooled_bytes = 0

    def start(self) -> None:
        _start_threads(
            self._executor, min(self.streams, len(self._pending))
        )
        self._thread.start()

    def consume_ended(self, refund: int) -> None:
        """A request's consume has ended (the consumes' loop): counted
        before the refund wakes the stage, which with nothing left in
        flight admits a head of any size."""
        self.consumed += 1
        self.budget.release(refund)

    def wake(self) -> None:
        """Budget came back (any thread)."""
        try:
            self._loop.call_soon_threadsafe(self._released.set)
        except RuntimeError:
            # The stage's loop has closed; an engine thread's release
            # after the run has nobody left to wake.
            pass

    def close(self) -> None:
        """Stop the stage (idempotent; after a sound run it has ended
        by itself): reads still in flight are cancelled, none is
        issued."""

        def _cancel() -> None:
            if self._run_task is not None:
                self._run_task.cancel()

        if self._thread.ident is None:
            # Never started: what __init__ opened is closed here.
            self._executor.shutdown(wait=False)
            self._loop.close()
            return
        try:
            self._loop.call_soon_threadsafe(_cancel)
        except RuntimeError:
            pass  # closed already: the stage ran to its end
        if self._thread.is_alive():
            self._thread.join(_READ_STAGE_JOIN_S)
            if self._thread.is_alive():
                logger.warning(
                    "the read stage's thread is still in a plug-in's "
                    "read %.0f s after it was cancelled",
                    _READ_STAGE_JOIN_S,
                )

    def _thread_main(self) -> None:
        try:
            self._run_task = self._loop.create_task(self._run())
            self._loop.run_until_complete(self._run_task)
        except asyncio.CancelledError:
            pass  # close() on a failed or cancelled run
        except BaseException as e:
            self._post(self._fail, e)
        finally:
            self._executor.shutdown(wait=False, cancel_futures=True)
            self._loop.close()

    def _post(self, callback: Callable[..., None], *args: Any) -> None:
        try:
            self._consumes_loop.call_soon_threadsafe(callback, *args)
        except RuntimeError:
            # The consumes' loop has gone (its run failed or was
            # cancelled while this read was in flight).
            logger.debug("read stage: nobody left to post to")

    async def _run(self) -> None:
        budget = self.budget
        reading: Set[asyncio.Future] = set()
        released: Optional[asyncio.Future] = None
        try:
            while self._pending or reading:
                self.budget_blocked = False
                while self._pending and len(reading) < self.streams:
                    consumer = self._pending[0].buffer_consumer
                    cost = consumer.get_consuming_cost_bytes()
                    # Cleared before the budget is read: a release that
                    # lands after the test below is then still seen.
                    self._released.clear()
                    nothing_in_flight = self.issued == self.consumed
                    if budget.value < cost and nothing_in_flight:
                        # Same straggler grace as the device scan of the
                        # consumes' loop: split-assembly buffers release
                        # host budget from executor threads after their
                        # consume task resolves.
                        while (
                            budget.value < cost
                            and await _straggler_release_landed(budget)
                        ):
                            pass
                    if budget.value < cost and not nothing_in_flight:
                        self.budget_blocked = True
                        break
                    rr = self._pending.popleft()
                    # Invariant the flow analysis cannot see: every
                    # charge is re-credited when the request's consume
                    # completes (execute_read_reqs) or by the consumer's
                    # deferred releaser, and the cell is per-pipeline-
                    # run: a failed run cancels its tasks and drops the
                    # cell, so no charge outlives the budget it was
                    # charged against.
                    # snapcheck: disable=resource-lifecycle -- cross-thread discharge: released at consume completion (execute_read_reqs) or via the consumer's deferred releaser; cell dies with the run
                    budget.charge(cost)
                    self.issued += 1
                    self.min_budget = min(self.min_budget, budget.value)
                    deferred = consumer.get_deferred_cost_bytes()
                    if deferred:
                        consumer.set_cost_releaser(budget.release)
                    # The consume-completion refund excludes the deferred
                    # portion, which the consumer releases itself.
                    reading.add(
                        asyncio.ensure_future(
                            self._read(rr, cost - deferred)
                        )
                    )
                if self.budget_blocked:
                    released = asyncio.ensure_future(self._released.wait())
                    reading.add(released)
                wait_t0 = time.monotonic()
                done, _ = await asyncio.wait(
                    reading, return_when=asyncio.FIRST_COMPLETED
                )
                if released is not None:
                    # A read was ready to be issued and the budget said
                    # no: the time until something gave way is stall.
                    self.stall_s += time.monotonic() - wait_t0
                    released.cancel()
                    reading.discard(released)
                    done.discard(released)
                    released = None
                reading -= done
                for task in done:
                    task.result()  # _read posts its failures; never raises
        finally:
            # close() on a failed or cancelled run: the reads in flight
            # are cancelled and seen out, so none is left pending when
            # the loop closes.
            for task in reading:
                task.cancel()
            if reading:
                await asyncio.wait(reading)

    def _lease_into(
        self, rr: ReadReq, nbytes: int, leases: List[Any]
    ) -> memoryview:
        """``IOReq.into`` of ``rr``, on the plug-in's thread: the buffer
        its payload is read into, leased at the first call (a retried
        read gets it again) and owned by the consumer from then on. It
        never waits for the pool: this stage's host budget holds what
        is read."""
        if not leases:
            lease = self._pool.acquire(nbytes, wait=False)
            rr.buffer_consumer.hold_read_lease(lease)
            leases.append(lease)
        return memoryview(leases[0].buffer)[:nbytes]

    async def _read(self, rr: ReadReq, refund: int) -> None:
        io_req = IOReq(path=rr.path, byte_range=rr.byte_range)
        leases: List[Any] = []
        if self._pool is not None and rr.buffer_consumer.reads_into_pool():
            # A range's destination is of its length; a whole object's,
            # which is read into the pool only where it is stored as its
            # bytes, of what its consumer consumes.
            if rr.byte_range is not None:
                nbytes = rr.byte_range[1] - rr.byte_range[0]
            else:
                nbytes = rr.buffer_consumer.get_consuming_cost_bytes()
            io_req.into = functools.partial(
                self._lease_into, rr, nbytes, leases
            )
        t0 = time.monotonic()
        if self.reads_in_flight == 0 and self._idle_since is not None:
            self.read_idle_s += t0 - self._idle_since
        self.reads_in_flight += 1
        failure: Optional[BaseException] = None
        try:
            with tracing.span("read", path=rr.path):
                await self._storage.read(io_req)
        except asyncio.CancelledError:
            raise
        except BaseException as e:  # snapcheck: disable=swallowed-exception -- raised by execute_read_reqs on the consumes' loop (faultline's SimulatedCrash is a BaseException)
            failure = e
        finally:
            ended = time.monotonic()
            self.reads_in_flight -= 1
            if self.reads_in_flight == 0:
                self._idle_since = ended
        if failure is not None:
            # Nothing more is issued; what is in flight runs out.
            self._pending.clear()
            self._post(self._fail, failure)
        else:
            payload = io_payload(io_req)
            # What the consumes' loop counts as read (``_deliver``).
            nbytes = len(payload)
            if not leases:
                self.unpooled_bytes += nbytes
            elif leases[0].reused:
                self.pool_hit_bytes += nbytes
            else:
                self.pool_miss_bytes += nbytes
            self._post(self._deliver, rr, payload, refund, ended - t0)


async def execute_read_reqs(
    read_reqs: List[ReadReq],
    storage: StoragePlugin,
    memory_budget_bytes: int,
    rank: int,
    device_budget_bytes: Optional[int] = None,
    stats: Optional[Dict[str, Any]] = None,
    progress: Optional[Any] = None,
) -> int:
    """Run the read→consume pipeline; returns total bytes read.

    ``device_budget_bytes`` bounds the DEVICE (HBM) bytes deposited by
    in-flight streamed consumes awaiting assembly (SURVEY §7 hard-part
    5: restores must respect HBM headroom, not just host RAM). None =
    unbounded. At least one consume always dispatches so an over-budget
    region cannot deadlock the pipeline; releases arrive through the
    consumers' device releasers when assembly frees the chunks.

    ``stats`` (optional) accumulates exact per-run aggregates for the
    flight recorder, as in :func:`execute_write_reqs`.
    """
    begin_ts = time.monotonic()
    stall_s = 0.0
    ops: Dict[str, Dict[str, Any]] = {}
    if progress is not None:
        progress.add_bytes_total(
            sum(
                r.buffer_consumer.get_consuming_cost_bytes()
                - r.buffer_consumer.get_deferred_cost_bytes()
                for r in read_reqs
            )
        )
        await progress.async_tick(force=True)

    # Largest LOGICAL objects first: a big object issued last would gate
    # the restore's tail all alone after the small reads drain (VERDICT
    # r4 #2). The key is the whole-object size (sort_key_bytes), NOT the
    # consuming cost: a split object's first sub-read carries the
    # assembly surcharge in its cost, and sorting by cost would float
    # EVERY object's first sub-read ahead of ALL siblings — putting all
    # assembly buffers live concurrently through repeated forced
    # admission (r5 review finding). Same-object sub-reads share one
    # key, so the stable sort keeps each object's group contiguous and
    # in order.
    def _sort_bytes(r: ReadReq) -> int:
        key = getattr(r.buffer_consumer, "sort_key_bytes", None)
        return key if key is not None else r.buffer_consumer.get_consuming_cost_bytes()

    pending = deque(sorted(read_reqs, key=lambda r: -_sort_bytes(r)))
    total = len(pending)
    consumable: deque = deque()  # (ReadReq, buf, host_refund, ready_t)
    # Consume micro-profile (snapxray): read_wait — a completed read's
    # payload queued behind budget/executor pressure before its consume
    # dispatched — is only measurable here, between the two pipeline
    # stages. The scope was opened by the restore root in this thread.
    profile = _cprof.current()
    consuming: Dict[asyncio.Task, int] = {}
    device_budget = _BudgetCell(
        device_budget_bytes if device_budget_bytes is not None else (1 << 62)
    )
    # Admissions held back on the device budget: when a scan first found
    # a consumable payload that HBM had no room for (by ``id`` of its
    # request; with the device bytes it asked for then: a region's later
    # sub-reads cost nothing once its first was admitted), until its
    # consume is dispatched. Each is a ``restore.device_budget_wait``
    # span and one of the report's ``device_budget_waits``.
    held_back_since: Dict[int, Tuple[float, int]] = {}
    device_waits = 0
    device_wait_s = 0.0
    bytes_read = 0
    delivered = 0
    read_failure: Optional[BaseException] = None
    # Set by the read stage's posts (a payload, a failure): what this
    # loop waits for besides its consumes.
    arrived = asyncio.Event()

    def _deliver(rr: ReadReq, buf: Any, refund: int, seconds: float) -> None:
        nonlocal bytes_read, delivered
        delivered += 1
        bytes_read += len(buf)
        _observe_op(
            ops,
            "read",
            seconds,
            len(buf),
            progress,
            # Credit the same cost units bytes_total summed (consuming
            # cost minus deferred).
            progress_bytes=refund,
        )
        consumable.append((rr, buf, refund, time.monotonic()))
        arrived.set()

    def _fail(error: BaseException) -> None:
        nonlocal read_failure
        if read_failure is None:
            read_failure = error
        arrived.set()

    executor = ThreadPoolExecutor(max_workers=_MAX_STAGING_THREADS)
    arrival: Optional[asyncio.Future] = None
    in_use_gauge = telemetry.gauge(
        _metric_names.SCHED_BUDGET_IN_USE, pipeline="read"
    )
    stalled_gauge = telemetry.gauge(
        _metric_names.SCHED_BUDGET_STALLED, pipeline="read"
    )
    # snapmem: host-cell bytes are transient host RAM; the device cell
    # tracks HBM deposits — real bytes, but not host RAM, so it is
    # registered external (visible in the domain table, excluded from
    # the committed/headroom math). Forecast the host-side burst before
    # the read storm starts.
    mem_domain = memwatch.register(
        "scheduler.read.host",
        cap_bytes=memory_budget_bytes,
        transient=True,
        watch_residual="used",
    )
    mem_device_domain = memwatch.register(
        "scheduler.read.device",
        cap_bytes=device_budget_bytes,
        transient=True,
        external=True,
    )
    memwatch.forecast(
        min(
            sum(
                r.buffer_consumer.get_consuming_cost_bytes()
                for r in read_reqs
            ),
            memory_budget_bytes,
        ),
        kind="restore",
    )
    stage = _ReadStage(pending, storage, memory_budget_bytes, _deliver, _fail)
    budget = stage.budget
    try:
        # Every thread this run needs exists before its first read is
        # issued: the consumes' (at most one a request), the stage's own
        # and those its plug-in reads run on (_ReadStage.start).
        _start_threads(executor, min(_MAX_STAGING_THREADS, total))
        stage.start()
        while delivered < total or consumable or consuming:
            if read_failure is not None:
                raise read_failure
            budget_blocked = False

            # Dispatch consumes under the device budget. The scan skips
            # past blocked entries (a region waiting for budget must not
            # head-of-line-block other regions' consumes, whose
            # completion is what releases budget). If NOTHING is in
            # flight, no future completion can release device bytes —
            # force-admit the head so progress is guaranteed; the
            # overrun is then bounded by that one region's in-assembly
            # bytes, which must fit HBM anyway as the restored array.
            while consumable:
                pick = None
                for i, (rr, _buf, _refund, _ready_t) in enumerate(
                    consumable
                ):
                    dcost = rr.buffer_consumer.get_device_cost_bytes()
                    if not dcost or device_budget.value >= dcost:
                        pick = i
                        break
                    held_back_since.setdefault(
                        id(rr), (time.monotonic(), dcost)
                    )
                if pick is None:
                    if stage.reads_in_flight or consuming:
                        # Device-budget wait is stall too: consumable
                        # work exists but cannot dispatch until budget
                        # frees.
                        budget_blocked = True
                        break
                    if (
                        await _straggler_release_landed(device_budget)
                        or stage.reads_in_flight
                    ):
                        # A deferred release from an engine thread beat
                        # the grace window (or the stage, found between
                        # two reads, has issued its next) — rescan
                        # before overrunning.
                        continue
                    pick = 0
                rr, buf, host_refund, ready_t = consumable[pick]
                del consumable[pick]
                if profile is not None:
                    profile.note(
                        "read_wait",
                        time.monotonic() - ready_t,
                        len(buf),
                    )
                consumer = rr.buffer_consumer
                dcost = consumer.get_device_cost_bytes()
                held_back = held_back_since.pop(id(rr), None)
                if held_back is not None:
                    since, asked = held_back
                    admitted = time.monotonic()
                    tracing.interval(
                        "restore.device_budget_wait",
                        since,
                        admitted,
                        path=rr.path,
                        bytes=asked,
                    )
                    device_waits += 1
                    device_wait_s += admitted - since
                if dcost:
                    device_budget.charge(dcost)
                    consumer.set_device_cost_releaser(device_budget.release)

                async def _consume(rr=rr, buf=buf):
                    t0 = time.monotonic()
                    with tracing.span("consume", path=rr.path, bytes=len(buf)):
                        await rr.buffer_consumer.consume_buffer(buf, executor)
                    _observe_op(
                        ops,
                        "consume",
                        time.monotonic() - t0,
                        len(buf),
                        progress,
                    )

                consume_task = asyncio.ensure_future(_consume())
                consuming[consume_task] = host_refund

            in_use_gauge.set(memory_budget_bytes - budget.value)
            mem_domain.set_used(
                max(0, memory_budget_bytes - budget.value),
                pinned_bytes=max(0, memory_budget_bytes - budget.value),
            )
            if device_budget_bytes is not None:
                mem_device_domain.set_used(
                    max(0, device_budget_bytes - device_budget.value),
                    pinned_bytes=max(
                        0, device_budget_bytes - device_budget.value
                    ),
                )
            stalled_gauge.set(
                1.0 if budget_blocked or stage.budget_blocked else 0.0
            )
            if arrival is None:
                arrival = asyncio.ensure_future(arrived.wait())
            wait_t0 = time.monotonic()
            done, _ = await asyncio.wait(
                set(consuming) | {arrival},
                return_when=asyncio.FIRST_COMPLETED,
            )
            if budget_blocked:
                stall_s += time.monotonic() - wait_t0
            if arrival in done:
                # The payloads are in ``consumable`` already (_deliver).
                done.discard(arrival)
                arrival = None
                arrived.clear()
            for task in done:
                cost = consuming.pop(task)
                task.result()  # propagate consume errors
                stage.consume_ended(cost)
            if progress is not None:
                await progress.async_tick()
    finally:
        if arrival is not None:
            arrival.cancel()
        stage.close()
        executor.shutdown(wait=False)
        in_use_gauge.set(0)
        stalled_gauge.set(0)
        mem_domain.set_used(max(0, memory_budget_bytes - budget.value))
        mem_domain.close()
        mem_device_domain.close()
    stall_s += stage.stall_s
    elapsed = time.monotonic() - begin_ts
    _merge_stats(
        stats,
        "read",
        bytes_read,
        stall_s,
        memory_budget_bytes - stage.min_budget,
        ops,
    )
    if stats is not None:
        stats["device_budget_waits"] = (
            stats.get("device_budget_waits", 0) + device_waits
        )
        stats["device_budget_wait_s"] = (
            stats.get("device_budget_wait_s", 0.0) + device_wait_s
        )
        stats["read_idle_s"] = stats.get("read_idle_s", 0.0) + stage.read_idle_s
        stats["read_streams"] = stage.streams
        for key, nbytes in (
            ("read_pool_hit_bytes", stage.pool_hit_bytes),
            ("read_pool_miss_bytes", stage.pool_miss_bytes),
            ("read_unpooled_bytes", stage.unpooled_bytes),
        ):
            stats[key] = stats.get(key, 0) + nbytes
    mbps = bytes_read / 1024 / 1024 / elapsed if elapsed > 0 else 0.0
    logger.info(
        "Rank %d finished loading (%d bytes). Throughput: %.2f MB/s",
        rank,
        bytes_read,
        mbps,
    )
    return bytes_read
