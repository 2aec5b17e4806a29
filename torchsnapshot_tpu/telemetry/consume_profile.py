"""Phase profile: sub-step attribution inside the restore's consume path
and inside the take's staging path, by one accumulator.

The flight recorder can say that a restore spent its time in ``consume``
and not in ``read``, and the benchmark that ``async_save`` blocked for
3.1 s (PERF.md section 5) — but not WHERE inside consume, or inside
staging, the time went. This module is that number: an always-on,
contextvar-scoped :class:`PhaseProfile` that the restore root (kind
``"consume"``) or the take root (kind ``"stage"``) opens and that every
buffer consumer, or every array stager, notes into at per-leaf/per-chunk
granularity. While ``tracing`` is enabled each note is also a span,
``consume.<sub-step>`` or ``stage.<sub-step>``.

======================  ================================================
restore (``consume.``)  what it times
======================  ================================================
read_wait               a completed read's payload sitting in the
                        scheduler queue before its consume dispatched
                        (budget / device-budget / executor pressure —
                        NOT part of consume wall)
executor_wait           consume dispatched → one of the consume
                        executor's threads starts it
view                    the length check and the ``np.frombuffer`` view
                        of a streamed part
h2d_submit              ``h2d_pipeline().submit`` as the consume thread
                        sees it, with the done-callback's registration
deserialize             pickled-object loads (``bytes_to_object``) and
                        raw byte→array reinterpretation
decode                  codec work: ``decompress_payload`` and chunk-
                        store codec decode (zlib/zstd/int8)
verify_wait             waiting for a streamed object's lock before a
                        crc fold (the fold is in order, so one object's
                        parts queue behind each other here)
verify                  integrity work alone: ``verify_checksum``, the
                        crc32 fold of a streamed object (without the
                        wait for its lock), content-fingerprint checks
reassemble              host memcpy: scattering chunk views into region
                        buffers, splicing ranged sub-reads into assembly
                        buffers
h2d_wait                a leaf's finalize waiting for the overlap engine
                        to land the transfers it was handed (inside the
                        consume that completed the leaf)
device_put              H2D transfers issued from INSIDE consume
                        executors (small-region batched puts at a
                        consume-triggered finalize)
staging_release         freeing assembly/staging buffers and re-crediting
                        scheduler budget reservations
pool_wait               waiting for a staging-pool buffer at pool
                        capacity (staging_pool.py)
loop_wait               the consume thread done → the event loop
                        resumes the consume task (the loop is
                        one thread; reads, consumes and progress ticks
                        all complete on it)
h2d_overlap             the overlap engine's H2D transfer wall
                        (ops/transfer.py H2DPipeline) — UNION time across
                        concurrent workers so bytes/seconds is delivered
                        link GB/s; concurrent with reads/consumes, NOT
                        part of consume wall
overlap_other           in-consume-named work that ran outside any
                        consume executor (engine-triggered finalize
                        placement, donation waits) — beside the wall
other                   consume wall the sub-steps above did not account
                        for — computed at collect time so the breakdown
                        SUMS to the consume wall exactly
======================  ================================================

======================  ================================================
take (``stage.``)       what it times (``ArrayBufferStager._stage_sync``
                        and ``ops/transfer.parallel_device_get._fetch``)
======================  ================================================
alloc                   a chunked leaf's assembly buffer: a lease of the
                        takes' pool (``staging_pool.py``; its span says
                        whether the pool had the buffer, ``pool`` "hit"
                        or "miss"), or an ``np.empty`` outside a take
slice                   dispatch of the device slice: one
                        ``jax.lax.slice_in_dim`` a chunk, and
                        ``data[chunk_slices]`` of a subdivided shard
d2h                     slice dispatched → bytes on the host
                        (``np.asarray``), a chunk or a whole small leaf
copy                    host memcpy: ``out[sel] = piece``,
                        ``np.ascontiguousarray``, the defensive copy of
                        user numpy memory
compress                ``compress_payload``
checksum                ``compute_checksum`` of the payload
fetch_wait              a staging thread waiting for its leaf's chunk
                        fetches on the D2H pool (their own slice / d2h /
                        copy are noted by the pool's threads)
clone                   ``device_clone_write_reqs`` in the capture
                        (span ``capture.clone``) — beside the wall
other                   the rest of the thread-seconds inside
                        ``_stage_sync`` and ``_fetch``: the block sums to
                        them exactly
======================  ================================================

A take's block also carries ``pool_hit_bytes`` and ``pool_miss_bytes``:
the bytes of assembly buffers the takes' pool had, their pages touched
by an earlier save of the process, and the bytes it allocated afresh.

Scoping matches the snapserve read-plane attribution: the profile is a
contextvar set in the restoring (or taking) thread; consumers and
stagers CAPTURE it at plan-build time — which happens in that thread —
so notes from executor threads, and from an async take's background
drain, land in the right operation even with two in flight. Cost when
nothing special is happening: one ``time.monotonic()`` pair and one lock
per noted sub-step per chunk; spans are emitted only while tracing is
enabled.
"""

import contextvars
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Optional

from .. import tracing

# Sub-steps that run INSIDE consume_buffer (their seconds reconcile
# against the scheduler's consume op seconds); the OVERLAP sub-steps
# happen outside the consume wall and are reported beside them:
# read_wait between read completion and consume dispatch, h2d_overlap
# on the H2D overlap engine's transfer threads (ops/transfer.py
# H2DPipeline) — device placement and buffer donation the streaming
# fast path moved OFF the consume executors so it rides concurrently
# with reads and decodes still in flight.
IN_CONSUME_SUBSTEPS = (
    "executor_wait",
    "view",
    "h2d_submit",
    "deserialize",
    "decode",
    "verify_wait",
    "verify",
    "reassemble",
    "h2d_wait",
    "device_put",
    "staging_release",
    "pool_wait",
    "loop_wait",
)
# Beside-the-wall buckets: read_wait (scheduler queueing), h2d_overlap
# (the overlap engine's transfers — union time, see overlap_span),
# overlap_other (in-consume-named work that ran OUTSIDE a consume
# section, e.g. an engine-triggered finalize's device placement and
# buffer donation — kept separate from h2d_overlap so the engine's
# delivered-GB/s certificate is never polluted by finalize bytes).
OVERLAP_SUBSTEPS = ("read_wait", "h2d_overlap", "overlap_other")
SUBSTEPS = OVERLAP_SUBSTEPS + IN_CONSUME_SUBSTEPS
# The take's side: thread-seconds inside ArrayBufferStager._stage_sync
# and parallel_device_get's _fetch (their walls are the block's
# ``stage_s``); ``clone`` is the capture's attempt at device clones,
# beside that wall.
STAGE_SUBSTEPS = (
    "alloc",
    "slice",
    "d2h",
    "copy",
    "compress",
    "checksum",
    "fetch_wait",
)
# kind -> the sub-steps whose seconds, with ``other``, sum to the wall.
_IN_WALL = {"consume": IN_CONSUME_SUBSTEPS, "stage": STAGE_SUBSTEPS}


class PhaseProfile:
    """Thread-safe sub-step accumulator for ONE restore (``kind``
    "consume") or ONE take ("stage"); the kind is the prefix of the
    spans its notes emit while tracing is enabled."""

    __slots__ = (
        "kind",
        "_lock",
        "_agg",
        "_wall_s",
        "_pool_bytes",
        "_pool_leased_now",
        "trace_id",
        "_ov_active",
        "_ov_start",
    )

    def __init__(self, kind: str = "consume") -> None:
        self.kind = kind
        self._lock = threading.Lock()
        # substep -> [count, seconds, bytes]
        self._agg: Dict[str, list] = {}
        # Thread-seconds the noting code itself spent (``wall``): the
        # take has no scheduler op that sums them, as the restore has.
        self._wall_s = 0.0
        # A take's assembly-buffer bytes the pool had / allocated.
        self._pool_bytes = {"hit": 0, "miss": 0}
        self._pool_leased_now = 0
        # Captured here so executor-thread sub-step spans can stamp the
        # operation's trace id without a contextvar handoff.
        self.trace_id = tracing.current_trace_id()
        # Union-time clock for the overlap engine: h2d_overlap seconds
        # count wall during which >= 1 transfer was in flight for THIS
        # restore — summing per-call walls across depth-N concurrent
        # workers would overstate seconds by up to the depth factor and
        # understate the delivered GB/s the certificate is built from.
        self._ov_active = 0
        self._ov_start = 0.0

    def note(self, substep: str, seconds: float, nbytes: int = 0) -> None:
        with self._lock:
            entry = self._agg.get(substep)
            if entry is None:
                entry = self._agg[substep] = [0, 0.0, 0]
            entry[0] += 1
            entry[1] += seconds
            entry[2] += nbytes

    def add_wall(self, seconds: float) -> None:
        with self._lock:
            self._wall_s += seconds

    def note_pool_lease(self, reused: bool, nbytes: int) -> int:
        """Count one assembly buffer leased from the takes' pool;
        returns the bytes this take holds leased now."""
        with self._lock:
            self._pool_bytes["hit" if reused else "miss"] += nbytes
            self._pool_leased_now += nbytes
            return self._pool_leased_now

    def note_pool_release(self, nbytes: int) -> None:
        with self._lock:
            self._pool_leased_now -= nbytes

    def _overlap_enter(self) -> None:
        with self._lock:
            if self._ov_active == 0:
                self._ov_start = time.monotonic()
            self._ov_active += 1

    def _overlap_exit(self, nbytes: int) -> None:
        with self._lock:
            self._ov_active -= 1
            entry = self._agg.get("h2d_overlap")
            if entry is None:
                entry = self._agg["h2d_overlap"] = [0, 0.0, 0]
            entry[0] += 1
            entry[2] += nbytes
            if self._ov_active == 0:
                entry[1] += time.monotonic() - self._ov_start

    def summary(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {
                substep: {
                    "count": entry[0],
                    "seconds": round(entry[1], 6),
                    "bytes": entry[2],
                }
                for substep, entry in sorted(self._agg.items())
            }

    def block(self, wall_s: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """The flight-report block: ``substeps`` (count, thread-seconds,
        bytes each), ``accounted_s`` and ``<kind>_s``, the wall they are
        held against, with an ``other`` sub-step so that the in-wall
        sub-steps plus ``other`` sum to the wall exactly. ``wall_s`` is
        the scheduler's consume op seconds for a restore; a take's wall
        is what :func:`wall` summed. None when nothing was noted (a
        restore of primitives only)."""
        substeps = self.summary()
        if wall_s is None:
            with self._lock:
                wall_s = self._wall_s or None
        if not substeps and not wall_s:
            return None
        accounted = sum(
            substeps.get(s, {}).get("seconds", 0.0)
            for s in _IN_WALL[self.kind]
        )
        block: Dict[str, Any] = {
            "substeps": substeps,
            "accounted_s": round(accounted, 6),
        }
        if self.kind == "stage":
            with self._lock:
                block["pool_hit_bytes"] = self._pool_bytes["hit"]
                block["pool_miss_bytes"] = self._pool_bytes["miss"]
        if wall_s is not None:
            block[f"{self.kind}_s"] = round(wall_s, 6)
            substeps["other"] = {
                "count": 0,
                "seconds": round(max(0.0, wall_s - accounted), 6),
                "bytes": 0,
            }
        return block


_SCOPE: "contextvars.ContextVar[Optional[PhaseProfile]]" = (
    contextvars.ContextVar("tpusnapshot_phase_profile", default=None)
)

# Consume-section marker (thread-local): consumer executor bodies wrap
# their work in consume_section() so sub-step notes can tell "inside a
# scheduler consume span" from "on the overlap side". The same code
# (e.g. ArrayRestorePlan.finalize) runs on either side depending on
# which completion fired last; an in-consume-named note recorded
# OUTSIDE a consume section is pipeline work that overlapped the
# consume wall, so it folds into ``overlap_other`` (NOT h2d_overlap —
# that bucket is reserved for the engine's own transfer clock) —
# keeping the in-consume sub-steps summing exactly to the consume wall.
_SECTION = threading.local()


@contextmanager
def consume_section():
    prev = getattr(_SECTION, "active", False)
    _SECTION.active = True
    try:
        yield
    finally:
        _SECTION.active = prev


def in_consume_section() -> bool:
    return getattr(_SECTION, "active", False)


def _route(profile: PhaseProfile, name: str) -> str:
    if (
        profile.kind == "consume"
        and name in IN_CONSUME_SUBSTEPS
        and not in_consume_section()
    ):
        return "overlap_other"
    return name


@contextmanager
def scope(kind: str) -> Iterator[PhaseProfile]:
    """Open one operation's profile in the restoring (taking) thread;
    the scope closes with the block, the profile lives as long as
    whoever captured it (an async take's drain notes into it after the
    call returned)."""
    profile = PhaseProfile(kind)
    token = _SCOPE.set(profile)
    try:
        yield profile
    finally:
        _SCOPE.reset(token)


def current(kind: str = "consume") -> Optional[PhaseProfile]:
    """The active profile of ``kind`` — captured by consumers and
    stagers at plan-build time. None outside a scope, and for a stager
    built inside a restore or a consumer built inside a take."""
    profile = _SCOPE.get()
    if profile is not None and profile.kind == kind:
        return profile
    return None


def _span_args(profile: PhaseProfile, nbytes: int) -> Dict[str, Any]:
    span_args: Dict[str, Any] = {"bytes": nbytes}
    if profile.trace_id is not None:
        span_args["trace"] = profile.trace_id
    return span_args


@contextmanager
def overlap_span(profile: Optional[PhaseProfile], nbytes: int = 0):
    """Time one overlap-engine transfer into ``h2d_overlap`` with
    UNION-time semantics: concurrent transfers for one restore advance
    the clock once, so bytes/seconds is the engine's delivered link
    throughput at any depth. Emits a ``consume.h2d_overlap`` span per
    transfer while tracing is on (spans may overlap — that is the
    point)."""
    if profile is None:
        yield
        return
    if tracing.enabled():
        with tracing.span(
            "consume.h2d_overlap", **_span_args(profile, nbytes)
        ):
            profile._overlap_enter()
            try:
                yield
            finally:
                profile._overlap_exit(nbytes)
        return
    profile._overlap_enter()
    try:
        yield
    finally:
        profile._overlap_exit(nbytes)


@contextmanager
def substep(
    profile: Optional[PhaseProfile], name: str, nbytes: int = 0
):
    """Time one sub-step into ``profile``. A plain passthrough when no
    scope is active (``profile`` None) — verify()/read_object paths
    reuse the instrumented consumers, and emitting ``consume.<name>``
    spans for them would hand summarize a bogus consume-breakdown
    section for an operation that never restored. While tracing is
    enabled, a ``<kind>.<name>`` span is emitted alongside the note,
    stamped with the operation's trace id even from executor threads."""
    if profile is None:
        yield
        return
    name = _route(profile, name)
    if tracing.enabled():
        with tracing.span(
            f"{profile.kind}.{name}", **_span_args(profile, nbytes)
        ):
            t0 = time.monotonic()
            try:
                yield
            finally:
                profile.note(name, time.monotonic() - t0, nbytes)
        return
    t0 = time.monotonic()
    try:
        yield
    finally:
        profile.note(name, time.monotonic() - t0, nbytes)


def note_interval(
    profile: Optional[PhaseProfile],
    name: str,
    begin: float,
    end: float,
    nbytes: int = 0,
    **span_args: Any,
) -> None:
    """Note a sub-step whose two ends the caller read off
    ``time.monotonic()`` itself: on different threads (a wait for an
    executor, for the event loop), where no ``with`` block can span it,
    or around a stretch whose outcome its span is to carry
    (``span_args``). Not routed: the caller knows on which side of the
    consume wall the stretch lies."""
    if profile is None:
        return
    profile.note(name, end - begin, nbytes)
    if tracing.enabled():
        tracing.interval(
            f"{profile.kind}.{name}",
            begin,
            end,
            **_span_args(profile, nbytes),
            **span_args,
        )


@contextmanager
def wall(profile: Optional[PhaseProfile]):
    """Count the block's thread-seconds into the profile's own wall
    (the take's ``stage_s``). No span: the scheduler's ``stage`` span
    already brackets it."""
    if profile is None:
        yield
        return
    t0 = time.monotonic()
    try:
        yield
    finally:
        profile.add_wall(time.monotonic() - t0)
