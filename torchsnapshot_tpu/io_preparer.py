"""IO preparers: map values ⇄ manifest entries + write/read requests.

TPU-native analog of reference torchsnapshot/io_preparer.py:37-401. Three
value classes:

- **dense arrays** (``numpy.ndarray``, fully-replicated or single-device
  ``jax.Array``) → ``ArrayEntry`` + one write of raw payload bytes;
- **sharded arrays** (``jax.Array`` partitioned over a mesh) →
  ``ShardedArrayEntry``; every addressable shard with ``replica_id == 0``
  is persisted by the process that owns it (this generalizes the
  reference's ShardedTensor handling, which has no replica dimension —
  SURVEY §7 "hard parts" #1), subdivided into ≤ ``MAX_CHUNK_SIZE_BYTES``
  chunks (reference io_preparer.py:38,40-72);
- **objects** (anything else picklable) → ``ObjectEntry`` (reference
  io_preparer.py:290-323), with small scalars inlined into the manifest as
  ``PrimitiveEntry`` (beyond parity — the reference writes one storage
  object per scalar).

Staging performs the HBM→host copy inside a thread executor; for
unsubdivided shards the async device→host copy is kicked off at prepare
time (``copy_to_host_async``) so transfers overlap with scheduling —
the TPU analog of the reference's CUDA-stream staging thread pool
(io_preparer.py:199-210).

Restore routes *all* array entries — dense or sharded — through a single
:class:`ArrayRestorePlan`, which computes the overlap of saved chunks with
the *target sharding's* addressable shards (``resharding.py``), reads only
the needed chunks (with ranged reads for contiguous overlaps), assembles
per-device host buffers, and builds the result with
``jax.make_array_from_single_device_arrays``. Elastic restore onto a
different mesh/pod shape is therefore the same code path as same-sharding
restore (reference analog: resharding.py:135-199 + io_preparer.py:113-163).
"""

import asyncio
import logging
import os
import threading
import time
from concurrent.futures import Executor
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import staging_pool, telemetry, tracing
from .telemetry import consume_profile as _cprof
from .telemetry import metrics as _metric_names
from .io_types import BufferConsumer, BufferStager, BufferType, ReadReq, WriteReq
from .utils.env import env_int
from .ops.transfer import (
    chunked_device_put,
    device_clone,
    h2d_chunk_bytes,
    h2d_pipeline,
    h2d_put_copies,
    parallel_device_get,
    should_chunk_h2d,
    should_chunk_transfer,
)
from .manifest import (
    ArrayEntry,
    Entry,
    ObjectEntry,
    PrimitiveEntry,
    Shard,
    ShardedArrayEntry,
)
from .resharding import (
    Overlap,
    compute_overlap,
    contiguous_byte_range,
    index_to_offsets_sizes,
    subdivide,
)
from .serialization import (
    ARRAY_SERIALIZER,
    OBJECT_SERIALIZER,
    StreamingCrc32,
    bytes_to_object,
    compress_payload,
    compute_checksum,
    decompress_payload,
    dtype_to_str,
    object_to_bytes,
    str_to_dtype,
    verify_checksum,
)

logger = logging.getLogger(__name__)

# Reference: io_preparer.py:38 (512 MB max shard chunk).
MAX_CHUNK_SIZE_BYTES: int = 512 * 1024 * 1024

# Whole-object reads above this size are split into concurrent ranged
# sub-reads reassembled on host (VERDICT r3 weak #3: a dense ArrayEntry
# is ONE storage object of unbounded size, and a single-stream download
# caps restore far below the link ceiling on object stores — the
# read-side mirror of the GCS composite upload; reference analog: 100 MB
# download chunks, reference gcs.py:55). Also the sub-read size.
_PARALLEL_READ_THRESHOLD_ENV_VAR = "TPUSNAPSHOT_PARALLEL_READ_THRESHOLD"
_DEFAULT_PARALLEL_READ_THRESHOLD = 64 * 1024 * 1024


def _parallel_read_threshold() -> int:
    return env_int(
        _PARALLEL_READ_THRESHOLD_ENV_VAR, _DEFAULT_PARALLEL_READ_THRESHOLD
    )


_DEVICE_BUDGET_ENV_VAR = "TPUSNAPSHOT_DEVICE_BUDGET_BYTES"


_KNOB_UNSET = object()


def _device_budget_knob() -> Any:
    """The explicit env knob: its bytes, None for 0 (unbounded), or
    ``_KNOB_UNSET`` where it is absent or malformed. A malformed value
    falls THROUGH to the autodetect (r5 review finding — mapping it to
    "unbounded" would strip exactly the protection the operator
    explicitly asked for)."""
    if os.environ.get(_DEVICE_BUDGET_ENV_VAR) is None:
        return _KNOB_UNSET
    value = env_int(_DEVICE_BUDGET_ENV_VAR, -1)
    if value > 0:
        return value
    return None if value == 0 else _KNOB_UNSET


def _runtime_free_bytes(device: Any = None) -> Optional[int]:
    """What the runtime reports free on ``device`` (default: the first)
    right now. TPUs do; CPU/virtual devices usually report nothing →
    None."""
    try:
        stats = (device or jax.devices()[0]).memory_stats() or {}
        limit = stats.get("bytes_limit")
        if limit:
            return max(int(limit - stats.get("bytes_in_use", 0)), 0)
    # memory_stats is an optional backend capability; absence means
    # "no device budget", the documented unbounded default.
    except Exception:  # snapcheck: disable=swallowed-exception -- capability probe
        pass
    return None


def _device_free_bytes(device: Any = None) -> Optional[int]:
    """HBM bytes a restore may fill on ``device``: the env knob where
    set (it stands in for "free"), else what the runtime reports."""
    knob = _device_budget_knob()
    return _runtime_free_bytes(device) if knob is _KNOB_UNSET else knob


def get_device_restore_budget_bytes() -> Optional[int]:
    """HBM bytes the restore pipeline may hold as in-flight streamed
    chunks awaiting assembly (SURVEY §7 hard-part 5). Explicit env knob
    wins (0 = unbounded); otherwise 90% of the device's currently free
    memory when the runtime reports it (TPUs do; CPU/virtual devices
    usually return None → unbounded)."""
    knob = _device_budget_knob()
    if knob is not _KNOB_UNSET:
        return knob
    free = _runtime_free_bytes()
    if free is None:
        return None
    return max(int(0.9 * free), 256 * 1024 * 1024)


def device_peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest local device, None where
    the backend reports none."""
    peaks = []
    for device in jax.local_devices():
        try:
            stats = device.memory_stats() or {}
        except Exception:  # snapcheck: disable=swallowed-exception -- capability probe
            continue
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks, default=None)


def template_placement(template: Any):
    """``(sharding, [(device, index), ...])`` of a device restore
    target: a ``jax.Array``, or a ``jax.ShapeDtypeStruct`` that carries
    a sharding (what a Stateful holds once it released its template's
    buffers, and what ``jax.eval_shape`` gives a caller that never made
    one). None for any other template."""
    if _is_jax_array(template):
        return template.sharding, [
            (shard.device, shard.index)
            for shard in template.addressable_shards
        ]
    if (
        isinstance(template, jax.ShapeDtypeStruct)
        and getattr(template, "sharding", None) is not None
    ):
        indices = template.sharding.addressable_devices_indices_map(
            tuple(template.shape)
        )
        return template.sharding, list(indices.items())
    return None


def abstract_of(value: Any) -> Any:
    """What a restore needs of a device template, without its buffers:
    a ``jax.Array`` as a ``jax.ShapeDtypeStruct`` with its sharding.
    Anything else, and typed PRNG keys (a few bytes, restored through
    their key data's layout), as it is."""
    if not _is_jax_array(value) or _is_prng_key_array(value):
        return value
    return jax.ShapeDtypeStruct(
        value.shape, value.dtype, sharding=value.sharding
    )


def forget_device_templates(flattened: Dict[str, Any]) -> int:
    """Replace every device array among ``flattened``'s values by its
    :func:`abstract_of`; returns the bytes no longer referenced here."""
    released = 0
    for path, value in flattened.items():
        abstract = abstract_of(value)
        if abstract is not value:
            released += int(value.nbytes)
            flattened[path] = abstract
    return released


def template_crowds_device(templates: Any) -> bool:
    """Whether the arrays about to land do not fit comfortably beside
    the device templates they replace: on some device, the bytes
    incoming plus one more copy of its largest shard (a streamed leaf's
    chunks live beside their concatenation) take more than half of what
    is free there now. Half, because the free bytes are not one block
    and the process allocates meanwhile; a state a quarter of HBM in
    size (template + landed = half) never comes near it. Devices that
    report no memory never say yes."""
    incoming: Dict[Any, int] = {}
    largest: Dict[Any, int] = {}
    for template in templates:
        if not _is_jax_array(template):
            continue
        for shard in template.addressable_shards:
            nbytes = int(shard.data.nbytes)
            incoming[shard.device] = incoming.get(shard.device, 0) + nbytes
            largest[shard.device] = max(largest.get(shard.device, 0), nbytes)
    for device, nbytes in incoming.items():
        free = _device_free_bytes(device)
        if free is not None and 2 * (nbytes + largest[device]) > free:
            return True
    return False


_PRIMITIVE_TYPES = (int, float, bool, str, complex, type(None))


def get_storage_path(rank: int, logical_path: str, replicated: bool) -> str:
    """Reference analog: io_preparer.py:336-342."""
    if replicated:
        return f"replicated/{logical_path}"
    return f"{rank}/{logical_path}"


def chunk_location(logical_path: str, offsets: List[int]) -> str:
    suffix = "_".join(str(o) for o in offsets)
    return f"sharded/{logical_path}_{suffix}" if suffix else f"sharded/{logical_path}_0"


def _is_jax_array(obj: Any) -> bool:
    return isinstance(obj, jax.Array)


def _is_prng_key_array(obj: Any) -> bool:
    return _is_jax_array(obj) and jax.dtypes.issubdtype(
        obj.dtype, jax.dtypes.prng_key
    )


def _is_partitioned(arr: jax.Array) -> bool:
    """True if the array's data is split across devices (vs replicated)."""
    return not arr.is_fully_replicated


# Chunked-transfer + clone primitives live in ops/transfer.py; private
# aliases keep this module's call sites short.
_should_chunk_transfer = should_chunk_transfer
_parallel_device_get = parallel_device_get


# Finalize executor: an eager finalize triggered from an H2D engine
# done-callback must NOT run on the engine worker itself —
# _await_pipeline blocks on futures queued on that same depth-limited
# pool, and at depth 1 (or N concurrent restores ≥ depth) the worker
# would wait on work only it can run. Engine-triggered finalizes hop
# here instead; the pool only ever waits ON the engine, never the
# reverse, so there is no cycle.
_finalize_pool: Optional[Any] = None
_finalize_pool_lock = threading.Lock()


def _get_finalize_pool():
    global _finalize_pool
    with _finalize_pool_lock:
        if _finalize_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            _finalize_pool = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="tpusnapshot-finalize"
            )
        return _finalize_pool


def _on_h2d_engine_thread() -> bool:
    return threading.current_thread().name.startswith("tpusnapshot-h2d")


async def _on_consume_executor(
    executor: Optional[Executor],
    body: Callable[[], Any],
    profile: Optional[Any],
    nbytes: int,
) -> Any:
    """Run a consume's blocking ``body`` on the scheduler's consume
    executor (inline without one) and note, into the restore's profile,
    the two hops that the consume wall holds besides the body:
    ``executor_wait``, dispatched → a thread of the executor starts it,
    and ``loop_wait``, body done → the event loop resumes this task."""
    if executor is None:
        return body()
    dispatched = started = ended = time.monotonic()

    def _timed() -> Any:
        nonlocal started, ended
        started = time.monotonic()
        try:
            return body()
        finally:
            ended = time.monotonic()

    try:
        return await asyncio.get_running_loop().run_in_executor(
            executor, _timed
        )
    finally:
        _cprof.note_interval(
            profile, "executor_wait", dispatched, started, nbytes
        )
        _cprof.note_interval(
            profile, "loop_wait", ended, time.monotonic(), nbytes
        )


class ArrayBufferStager(BufferStager):
    """Stages a device (or host) array into raw payload bytes.

    ``data`` is a single-device ``jax.Array`` (a shard's ``.data``) or a
    ``numpy.ndarray``. When ``chunk_slices`` is given, only that sub-box is
    staged (used when a shard is subdivided): the slice executes on device
    so only chunk-sized host memory is allocated.
    """

    # The pooled assembly buffer a chunked leaf's payload lives in, from
    # staging until the write pipeline lets go of the payload
    # (``release_staged``). A class default: ``chunkstore.ChunkStager``
    # has an ``__init__`` of its own and never leases.
    _lease: Optional[staging_pool.StagingLease] = None

    def __init__(
        self,
        data: Any,
        chunk_slices: Optional[Tuple[slice, ...]] = None,
        nbytes: Optional[int] = None,
        entry: Optional[ArrayEntry] = None,
        compression: Optional[str] = None,
        eager_host_copy: bool = True,
    ) -> None:
        self._data = data
        self._chunk_slices = chunk_slices
        self._compression = compression
        self._entry = entry  # back-patched with the payload checksum
        self._owns_data = False  # True once rebound to a private copy
        if nbytes is None:
            nbytes = int(np.dtype(data.dtype).itemsize * np.prod(data.shape))
        self._nbytes = nbytes
        # The take's phase profile, captured where the stager is built
        # (the taking thread): an async take's drain stages on threads
        # of its own, after the call returned.
        self._profile = _cprof.current("stage")
        if eager_host_copy:
            # Small arrays: start the whole-array async copy now so the
            # transfer overlaps with scheduling. Large arrays skip this —
            # they stage via parallel chunked transfers instead, and a
            # prepare-time whole-array copy would occupy the link with a
            # slow single stream. Async takes pass eager_host_copy=False:
            # a device-staged cut rebinds stagers to on-device clones, and
            # a transfer started on the original would never be consumed.
            # Incremental takes also pass False — a dedup hit must skip
            # the transfer entirely; apply_incremental kicks off copies
            # for the SURVIVING requests afterwards.
            self.kickoff_host_copy()

    def kickoff_host_copy(self) -> None:
        """Dispatch the async device→host copy for a small whole-array
        payload (no-op for chunked/sliced/host data or once staged)."""
        data = self._data
        if (
            data is not None
            and _is_jax_array(data)
            and self._chunk_slices is None
            and not _should_chunk_transfer(data)
        ):
            try:
                data.copy_to_host_async()
            # Pure prefetch hint: the later synchronous stage re-runs
            # the transfer and surfaces any real failure.
            except Exception:  # pragma: no cover; snapcheck: disable=swallowed-exception -- prefetch hint
                pass

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        loop = asyncio.get_running_loop()
        if executor is None:
            # Inline-staging escape hatch: every pipeline path passes an
            # executor; a caller opting out owns the stall trade-off.
            return self._stage_sync()  # snapcheck: disable=event-loop-blocking -- executor=None is the caller-owned inline path; all pipeline call sites pass an executor
        return await loop.run_in_executor(executor, self._stage_sync)

    def _stage_sync(self) -> BufferType:
        # Every stretch below is a sub-step of the take's phase profile
        # (telemetry/consume_profile.py): a note a leaf, and a
        # ``stage.<name>`` span while tracing is enabled.
        with _cprof.wall(self._profile):
            return self._stage_phases()

    def _stage_phases(self) -> BufferType:
        profile = self._profile
        data = self._data
        nbytes = self._nbytes
        if self._chunk_slices is not None:
            with _cprof.substep(profile, "slice", nbytes):
                data = data[self._chunk_slices]
        if _should_chunk_transfer(data):
            host = _parallel_device_get(
                data, profile, out=self._lease_assembly_buffer(data)
            )
        else:
            with _cprof.substep(profile, "d2h", nbytes):
                host = np.asarray(data)  # D2H for jax arrays; no-op for numpy
        with _cprof.substep(profile, "copy", nbytes):
            host = np.ascontiguousarray(host)
            if (
                isinstance(self._data, np.ndarray)
                and not self._owns_data
                and np.shares_memory(host, self._data)
            ):
                # User-owned mutable host memory: copy so the staged buffer
                # is a consistent cut (jax.Arrays are immutable — no copy
                # needed).
                host = host.copy()
        # Drop the source reference: once the payload is on host, the
        # device buffer (ours after a device-staged async take, or the
        # caller's) no longer needs to be pinned by this stager.
        self._data = None
        # Reinterpret as raw bytes: ml_dtypes dtypes (bfloat16, float8_*)
        # don't export the buffer protocol directly, but a uint8 view does,
        # and it is zero-copy.
        payload = memoryview(host.reshape(-1).view(np.uint8))
        if self._compression is not None:
            with _cprof.substep(profile, "compress", nbytes):
                payload = compress_payload(payload, self._compression)
            if self._entry is not None:
                self._entry.compression = self._compression
            # The compressed payload is bytes of its own.
            self.release_staged()
        if self._entry is not None:
            # The checksum reaches the persisted metadata because staging
            # always precedes the manifest consolidation: sync takes write
            # (hence stage) before the manifest all-gather; async takes
            # serialize each rank's manifest into its completion marker
            # only after execute_write_reqs finishes (snapshot.py _drain) —
            # staging may run entirely in that background drain under a
            # device-staged cut.
            with _cprof.substep(profile, "checksum", len(payload)):
                self._entry.checksum = compute_checksum(payload)
        return payload

    def _lease_assembly_buffer(self, data: Any) -> Optional[np.ndarray]:
        """The host array a chunked leaf is assembled in, leased from
        the takes' pool (``staging_pool.py``): after a process's first
        save its pages are touched already. None outside a take, where
        nothing would say how much one take has leased: the gather
        allocates, as it always did."""
        profile = self._profile
        if profile is None:
            return None
        nbytes = self._nbytes
        pool = staging_pool.get_take_staging_pool()
        began = time.monotonic()
        # Held by the stager from here on: ``release_staged``.
        self._lease = pool.acquire(nbytes)
        lease = self._lease
        # What stays between saves is at most what one take held
        # leased at once: the whole capture where it is staged inside
        # the call, a budget's worth where a sync take is held to one.
        pool.retain_up_to(profile.note_pool_lease(lease.reused, nbytes))
        out = lease.as_array(np.dtype(data.dtype), list(data.shape))
        _cprof.note_interval(
            profile,
            "alloc",
            began,
            time.monotonic(),
            nbytes,
            pool="hit" if lease.reused else "miss",
        )
        return out

    def release_staged(self) -> None:
        lease, self._lease = self._lease, None
        if lease is not None:
            lease.release()
            self._profile.note_pool_release(lease.nbytes)

    def get_staging_cost_bytes(self) -> int:
        return self._nbytes


def device_clone_write_reqs(write_reqs: List[WriteReq]) -> bool:
    """Rebind every array stager to a private on-device copy of its data.

    The consistent-cut primitive behind device-staged async snapshots: an
    HBM→HBM copy runs at memory bandwidth (orders of magnitude faster than
    device→host), so cloning the checkpoint state on device and draining
    the device→host staging in the background reduces the training stall
    from "one full D2H of the app state" to "one HBM copy". The clones own
    their buffers, so a subsequent training step that donates/deletes the
    source arrays (jit donation) cannot invalidate the snapshot.

    Host-side numpy data is copied on host (it is mutable user memory).
    Returns False — with all partial clones released — if the device ran
    out of memory; the caller falls back to host staging.
    """
    sources: Dict[int, Any] = {}
    rebinds: List[Tuple[ArrayBufferStager, int]] = []
    host_copies: Dict[int, Any] = {}
    for wr in write_reqs:
        stager = wr.buffer_stager
        if not isinstance(stager, ArrayBufferStager) or stager._data is None:
            continue
        data = stager._data
        if _is_jax_array(data):
            sources.setdefault(id(data), data)
            rebinds.append((stager, id(data)))
        elif isinstance(data, np.ndarray):
            # Dedupe by identity: a chunked dense array shares ONE
            # source across its chunk stagers — copy it once, not once
            # per chunk.
            key = id(data)
            if key not in host_copies:
                host_copies[key] = np.array(data, copy=True)
            stager._data = host_copies[key]
            stager._owns_data = True
    order = list(sources)
    clones = device_clone([sources[k] for k in order])
    if clones is None:
        logger.warning(
            "Device-staged snapshot does not fit in device memory; "
            "falling back to host staging."
        )
        return False
    clone_by_key = dict(zip(order, clones))
    for stager, key in rebinds:
        stager._data = clone_by_key[key]
        stager._owns_data = True
    return True


class ObjectBufferStager(BufferStager):
    def __init__(
        self,
        obj: Any,
        entry: Optional[ObjectEntry] = None,
        compression: Optional[str] = None,
    ) -> None:
        # Objects are small (counters, RNG states, dataloader cursors);
        # pickle eagerly so the staging cost is exact. Compression and
        # checksum are deferred to stage time: non-owner ranks of a
        # replicated object drop their write request without staging, so
        # they never pay those costs (their manifest entry legitimately
        # carries checksum/compression = None; the restore path prefers
        # the stripe owner's checksum-bearing entry).
        self._buf: BufferType = object_to_bytes(obj)
        self._entry = entry
        self._compression = compression
        self._staged = False

    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        if not self._staged:
            self._staged = True
            if self._compression is not None:
                self._buf = compress_payload(self._buf, self._compression)
                if self._entry is not None:
                    self._entry.compression = self._compression
            if self._entry is not None:
                self._entry.checksum = compute_checksum(self._buf)
        return self._buf

    def get_staging_cost_bytes(self) -> int:
        return len(self._buf)


class ObjectBufferConsumer(BufferConsumer):
    """Materializes a pickled object and hands it back via callback
    (reference io_preparer.py:290-304: objects cannot be restored in place).
    """

    def __init__(
        self,
        callback: Callable[[Any], None],
        size_hint: int = 1 << 20,
        checksum: Optional[str] = None,
        compression: Optional[str] = None,
    ):
        self._callback = callback
        self._size_hint = size_hint
        self._checksum = checksum
        self._compression = compression
        # Consume micro-profile scope, captured at plan-build time (the
        # restoring thread) so executor-thread notes attribute to the
        # right restore (telemetry/consume_profile.py).
        self._profile = _cprof.current()

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        def _load() -> Any:
            with _cprof.consume_section():
                with _cprof.substep(self._profile, "verify", len(buf)):
                    verify_checksum(buf, self._checksum)
                if self._compression is not None:
                    with _cprof.substep(self._profile, "decode", len(buf)):
                        raw = decompress_payload(buf, self._compression)
                else:
                    raw = buf
                with _cprof.substep(
                    self._profile, "deserialize", len(raw)
                ):
                    return bytes_to_object(raw)

        obj = await _on_consume_executor(
            executor, _load, self._profile, len(buf)
        )
        self._callback(obj)

    def get_consuming_cost_bytes(self) -> int:
        return self._size_hint


class _TargetRegion:
    """One distinct region of the global array needed on restore, with the
    devices that need it (replicas share one host buffer).

    The host buffer is LAZY and (for device-template restores) pooled:
    it materializes from the staging pool on the first scatter into it,
    so regions that end up streaming to device or adopting a zero-copy
    payload view never allocate one, and the ones that do allocate
    reuse a prior restore's buffer of the same size."""

    def __init__(
        self,
        offsets: List[int],
        sizes: List[int],
        dtype: np.dtype,
        poolable: bool = False,
    ):
        self.offsets = offsets
        self.sizes = sizes
        self.dtype = np.dtype(dtype)
        self.devices: List[Any] = []
        self.nbytes = int(self.dtype.itemsize * np.prod(sizes))
        # Lazily materialized host buffer (None until first needed). A
        # zero-copy adoption replaces it with a read-payload view
        # without ever touching the pool; host-template restores
        # allocate plain arrays (the buffer is handed to the app, so
        # pool reuse would alias user memory).
        self.buffer: Optional[np.ndarray] = None
        self._poolable = poolable
        self._lease: Optional[staging_pool.StagingLease] = None
        self._buf_lock = threading.Lock()
        # Whether the scheduler's device budget already holds this
        # region's reservation (charged once, by the first admitted
        # streaming sub-read; the unit of HBM occupancy is the region —
        # its chunks stay deposited until assembly).
        self.device_charged = False
        # Streaming reads leave the region's data on device as 1-D
        # chunks keyed by their flat byte offset within the region
        # (finalize concatenates + reshapes on device instead of a host
        # device_put). Distinct keys, so concurrent chunk streams
        # deposit without a region lock (GIL-atomic dict writes).
        self.device_chunks: Optional[Dict[int, Any]] = None
        # (release_cb, nbytes) pairs invoked by finalize once the
        # deposited chunks are concatenated and freed — returns the
        # streamed bytes to the scheduler's device-memory budget.
        self.device_releases: List[Tuple[Callable[[int], None], int]] = []
        # Chunk-copies still expected to scatter into this region; set
        # by the plan at build time. When the count drains the plan may
        # dispatch this region's H2D on the overlap engine instead of
        # waiting for plan finalize (chunk-granular overlap).
        self.pending_copies = 0
        # Future from the overlap engine's early dispatch (single-
        # device regions); finalize collects it instead of device_put.
        self.early_put: Optional[Any] = None

    def ensure_buffer(self, profile: Optional[Any] = None) -> np.ndarray:
        with self._buf_lock:
            if self.buffer is None:
                pool = (
                    staging_pool.get_staging_pool()
                    if self._poolable
                    else None
                )
                if pool is not None:
                    self._lease = pool.acquire(self.nbytes, profile)
                    self.buffer = self._lease.as_array(
                        self.dtype, list(self.sizes)
                    )
                else:
                    self.buffer = np.empty(self.sizes, dtype=self.dtype)
            return self.buffer

    def adopt(
        self,
        view: np.ndarray,
        lease: Optional[staging_pool.StagingLease] = None,
    ) -> None:
        """Take ``view``, a chunk's payload that exactly covers this
        region, as the buffer, with no copy; ``lease``, the pooled
        buffer under it, goes back by :meth:`release_lease` as a buffer
        from :meth:`ensure_buffer` does."""
        with self._buf_lock:
            self.buffer = view
            self._lease = lease

    def release_lease(self) -> None:
        """Return the pooled backing (if any) — only safe once no
        pending transfer still reads from ``buffer``."""
        with self._buf_lock:
            lease, self._lease = self._lease, None
            self.buffer = None if lease is not None else self.buffer
        if lease is not None:
            lease.release()


def _covers_region(
    view_shape: List[int],
    region: _TargetRegion,
    region_slices: Tuple[slice, ...],
    view_slices: Tuple[slice, ...],
) -> bool:
    """Whether a chunk of ``view_shape`` whose overlap with ``region`` is
    ``region_slices`` / ``view_slices`` is exactly the region."""
    return list(view_shape) == list(region.sizes) and all(
        sl.start == 0 and sl.stop == dim
        for slices in (region_slices, view_slices)
        for sl, dim in zip(slices, region.sizes)
    )


class _ChunkCopyConsumer(BufferConsumer):
    """Consumes one saved chunk's payload (possibly a ranged read) and
    scatters it into the overlapping target-region buffers."""

    def __init__(
        self,
        view_shape: List[int],
        dtype: np.dtype,
        copies: List[Tuple[_TargetRegion, Tuple[slice, ...], Tuple[slice, ...]]],
        checksum: Optional[str] = None,
        compression: Optional[str] = None,
        on_done: Optional[Callable[[], None]] = None,
        allow_adopt: bool = True,
        region_notify: Optional[Callable[[_TargetRegion], None]] = None,
        whole_object: Optional[str] = None,
    ) -> None:
        # copies: (region, region_slices, view_slices)
        self._view_shape = view_shape
        self._dtype = dtype
        self._copies = copies
        self._checksum = checksum
        self._compression = compression
        self._on_done = on_done
        # False when the payload handed to consume_buffer is a view over
        # a POOLED assembly buffer (split/content-chunk read states):
        # adopting such a view would pin pool memory past its release
        # and corrupt a later restore that reuses it.
        self._allow_adopt = allow_adopt
        # Plan hook: fired once per (this chunk, region) scatter so the
        # plan can early-dispatch a fully-populated region's H2D on the
        # overlap engine instead of waiting for finalize.
        self._region_notify = region_notify
        self._cost = int(np.dtype(dtype).itemsize * np.prod(view_shape))
        self._profile = _cprof.current()
        # The stored object's location where this consumer's read
        # returns it whole (None: a range of it, a part or a content
        # chunk): its length is checked, and it names the object.
        self._whole_object = whole_object
        # Whether the chunk exactly covers its one region, whose buffer
        # it can then be (adoption, below).
        self._covers = len(copies) == 1 and _covers_region(
            view_shape, *copies[0]
        )
        # The pooled buffer the payload was read into (IOReq.into), until
        # the region adopts it or it goes back.
        self._read_lease: Optional[staging_pool.StagingLease] = None

    def reads_into_pool(self) -> bool:
        # A whole object, stored as its bytes, that becomes its region's
        # buffer: the region gives the buffer back once the put that
        # copies it has landed (early put or finalize).
        return (
            self._whole_object is not None
            and self._compression is None
            and self._allow_adopt
            and self._covers
            and self._copies[0][0]._poolable
        )

    def hold_read_lease(self, lease: Any) -> None:
        self._read_lease = lease

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        def _copy() -> None:
            # Owned here from now on: adopted by the region, or given
            # back on every other path (a raise included).
            lease, self._read_lease = self._read_lease, None
            adopted = False
            try:
                adopted = _scatter(lease)
            finally:
                if lease is not None and not adopted:
                    lease.release()

        def _scatter(lease: Optional[staging_pool.StagingLease]) -> bool:
            """Verify and place the payload; whether a region adopted it."""
            if self._whole_object is not None and self._compression is None:
                nbytes = memoryview(buf).nbytes
                if nbytes != self._cost:
                    raise RuntimeError(
                        f"Read of {self._whole_object!r} returned {nbytes} "
                        f"bytes where its entry holds {self._cost}: the "
                        f"stored object is truncated or was replaced"
                    )
            with _cprof.substep(self._profile, "verify", len(buf)):
                verify_checksum(buf, self._checksum)
            if self._compression is not None:
                with _cprof.substep(self._profile, "decode", len(buf)):
                    buf_raw = decompress_payload(buf, self._compression)
            else:
                buf_raw = buf
            with _cprof.substep(self._profile, "reassemble", self._cost):
                view = np.frombuffer(buf_raw, dtype=self._dtype).reshape(
                    self._view_shape
                )
                for region, region_slices, view_slices in self._copies:
                    if (
                        self._allow_adopt
                        and self._covers
                        and region.buffer is None
                    ):
                        # The chunk exactly covers this region: adopt the
                        # zero-copy view instead of memcpy-ing into a
                        # staging buffer (np.frombuffer views are
                        # read-only, which device_put accepts), with the
                        # pooled buffer under it where there is one.
                        region.adopt(view, lease)
                        return True
                    region.ensure_buffer(self._profile)[
                        region_slices
                    ] = view[view_slices]
            return False

        def _copy_and_signal() -> None:
            with _cprof.consume_section():
                _copy()
                if self._region_notify is not None:
                    for region, _rs, _vs in self._copies:
                        self._region_notify(region)
                # Runs in the executor thread: a finalize triggered here
                # (host→device assembly) overlaps with reads still in
                # flight instead of blocking the event loop.
                if self._on_done is not None:
                    self._on_done()

        await _on_consume_executor(
            executor, _copy_and_signal, self._profile, len(buf)
        )

    def get_consuming_cost_bytes(self) -> int:
        return self._cost


class _PooledAssemblyState:
    """Shared lease/budget plumbing for read states that assemble ONE
    stored object in a host buffer drawn from the staging pool
    (``staging_pool.py``): the scheduler's deferred-cost releaser
    (charged as the first sub-read's/chunk's deferred cost) is
    re-credited exactly ONCE — when the buffer actually returns to the
    pool — whichever of buffer acquisition and the scheduler's
    dispatch hook lands first, so concurrent reads cannot overrun the
    budget and a pooled, multi-sub-read buffer cannot over-credit it.
    One implementation, two subclasses: the split whole-object path and
    the content-chunk (chunkstore) path must never diverge on this
    contract."""

    def __init__(self, nbytes: int) -> None:
        self.nbytes = nbytes
        self._buf: Optional[bytearray] = None  # allocated on first absorb
        self._lease: Optional[staging_pool.StagingLease] = None
        self._lock = threading.Lock()
        self._profile = _cprof.current()
        self._cost_release: Optional[Callable[[int], None]] = None

    def set_cost_releaser(self, release: Callable[[int], None]) -> None:
        with self._lock:
            lease = self._lease
            if lease is None:
                self._cost_release = release
        if lease is not None:
            # Acquisition raced ahead of the scheduler's dispatch hook:
            # hand the credit to the lease (fired once, at release).
            lease.set_budget_release(release, self.nbytes)

    def _ensure_buf(self) -> None:
        """Materialize the shared assembly buffer (pooled when the
        staging pool is enabled; the lease then carries the budget
        re-credit and fires it exactly once at pool return)."""
        with self._lock:
            if self._buf is not None:
                return
            pool = staging_pool.get_staging_pool()
            if pool is None:
                self._buf = bytearray(self.nbytes)
                return
            lease = pool.acquire(self.nbytes, self._profile)
            # Store the lease before touching anything else: until it
            # is reachable from self, an exception here would orphan
            # the pooled buffer (and its exactly-once budget re-credit).
            self._lease = lease
            self._buf = lease.buffer
            release, self._cost_release = self._cost_release, None
        if release is not None:
            lease.set_budget_release(release, self.nbytes)

    def _release_assembly_buffer(self) -> None:
        """Free the assembly buffer: pooled buffers return to the pool
        (which fires the budget re-credit once); plain ones re-credit
        through the releaser directly. Idempotent either way."""
        with self._lock:
            lease, self._lease = self._lease, None
            release, self._cost_release = self._cost_release, None
            self._buf = None
        if lease is not None:
            if release is not None:
                # _ensure_buf stored the lease but raised before
                # handing it the releaser: attach before releasing so
                # the budget re-credit still fires (exactly once — the
                # lease owns it from here).
                lease.set_budget_release(release, self.nbytes)
            lease.release()
        elif release is not None:
            release(self.nbytes)


class _SplitObjectReadState(_PooledAssemblyState):
    """Reassembles concurrent ranged sub-reads of ONE stored object into
    a single host buffer, then runs the real consumer on the whole
    payload. Checksum verification still covers the complete object (the
    inner consumer sees exactly the bytes a whole-object read would
    have), so splitting is integrity-preserving — unlike partial ranged
    reads, which skip verification."""

    def __init__(self, nbytes: int, inner: BufferConsumer) -> None:
        super().__init__(nbytes)
        self._inner = inner
        self._remaining = 0

    def extra_first_cost_bytes(self) -> int:
        """Cost charged on top of the first sub-read's payload: the
        shared host assembly buffer."""
        return self.nbytes

    def deferred_cost_bytes(self, first: bool, part_nbytes: int) -> int:
        """Portion of a sub-read's consuming cost whose allocation
        outlives its consume: the assembly buffer, carried by the first
        sub-read, freed when the LAST one lands."""
        return self.nbytes if first else 0

    def add_sub_reads(self, path: str, part_size: int) -> List[ReadReq]:
        reqs = []
        starts = list(range(0, self.nbytes, part_size))
        self._remaining = len(starts)
        for i, start in enumerate(starts):
            end = min(start + part_size, self.nbytes)
            reqs.append(
                ReadReq(
                    path=path,
                    buffer_consumer=_SubRangeConsumer(
                        self, start, end, first=(i == 0)
                    ),
                    byte_range=(start, end),
                )
            )
        return reqs

    async def absorb(
        self,
        start: int,
        end: int,
        buf: BufferType,
        executor: Optional[Executor] = None,
    ) -> None:
        def _copy() -> None:
            with _cprof.consume_section():
                self._ensure_buf()
                with _cprof.substep(
                    self._profile, "reassemble", end - start
                ):
                    if len(buf) != end - start:
                        raise RuntimeError(
                            f"Ranged sub-read returned {len(buf)} bytes for "
                            f"[{start}, {end}) — object shorter than the manifest "
                            f"implies (truncated or torn)."
                        )
                    # Disjoint ranges: concurrent executor threads never overlap.
                    memoryview(self._buf)[start:end] = buf

        await _on_consume_executor(executor, _copy, self._profile, len(buf))
        with self._lock:
            self._remaining -= 1
            last = self._remaining == 0
        if last:
            try:
                await self._inner.consume_buffer(
                    memoryview(self._buf)[: self.nbytes], executor
                )
            finally:
                with _cprof.consume_section(), _cprof.substep(
                    self._profile, "staging_release", self.nbytes
                ):
                    # Pool return fires the scheduler budget re-credit
                    # exactly once, however many sub-reads shared it.
                    self._release_assembly_buffer()


class _StreamingSplitState(_SplitObjectReadState):
    """Split read of one large object that STREAMS each completed
    sub-range to the target device instead of waiting for full host
    reassembly — overlapping storage reads with H2D transfers, which a
    reassemble-then-put split serializes (measured: a pure 640 MiB
    restore reached only 0.74 of the bracketed H2D ceiling because the
    last sub-read gated the entire device transfer).

    Fastlane: the H2D itself runs on the overlap ENGINE
    (ops/transfer.py H2DPipeline), not inside the consume executor — a
    consume here is only the length check, the incremental crc fold,
    and the transfer submit, so consume wall tracks host work while the
    double-buffered engine keeps the link saturated. The engine's
    done-callback deposits the device chunk and fires the plan's
    on_done once every part has BOTH crc-verified and landed on device.

    Only used when one uncompressed chunk exactly covers one
    single-device region (the dominant shape: restoring a large dense
    parameter). Integrity is unchanged: the crc32 is folded INCREMENTALLY
    over the in-order byte stream as sub-ranges land (out-of-order
    arrivals stash until their prefix completes — no full host
    reassembly, and no end-of-stream hash pass on the critical path) and
    checked BEFORE the plan's finalize exposes the array; the device
    chunks are unreachable until then, and a mismatch raises with
    nothing exposed."""

    def __init__(
        self,
        nbytes: int,
        region: "_TargetRegion",
        dtype: np.dtype,
        checksum: Optional[str],
        on_done: Callable[[], None],
        flat_base: int = 0,
        register_transfer: Optional[Callable[[Any], None]] = None,
    ) -> None:
        super().__init__(nbytes, inner=None)  # inner unused
        self._region = region
        self._np_dtype = dtype
        self._checksum = checksum
        self._on_done = on_done
        self._device = region.devices[0]
        # Byte offset of this stored object within the region's flat
        # layout: format-chunked dense arrays stream SEVERAL objects
        # into one region, each depositing at flat_base + sub-offset
        # (VERDICT r4 #2 — streaming used to engage only when one object
        # exactly covered the region).
        self._flat_base = flat_base
        if region.device_chunks is None:
            region.device_chunks = {}
        # Incremental crc (same no-op contract as verify_checksum for
        # absent/unknown-algorithm checksums).
        self._crc: Optional[StreamingCrc32] = (
            StreamingCrc32()
            if checksum and checksum.startswith("crc32:")
            else None
        )
        self._next_off = 0
        self._stash: Dict[int, BufferType] = {}
        # Held across a part's crc32 fold (45 ms a 64 MiB part), and by
        # nothing else: the stash, the offset and the crc are the
        # fold's own. ``self._lock`` guards the small state that the
        # event loop's thread and the overlap engine's callbacks touch,
        # and is never held across a fold, so neither waits for one.
        self._fold_lock = threading.Lock()
        self._released = 0  # deferred bytes already re-credited
        self._device_release: Optional[Callable[[int], None]] = None
        self._deposited = 0  # device bytes charged by the scheduler
        # Plan hook: every engine future is registered so finalize can
        # surface a transfer failure before publishing anything.
        self._register_transfer = register_transfer
        # Per-part budget refcounts: a part's payload is re-credited
        # only after BOTH holds drop — the crc prefix drain (the
        # out-of-order stash) and the overlap engine's transfer.
        self._part_refs: Dict[int, int] = {}
        # The pooled buffers the parts were read into (``IOReq.into``),
        # by offset: back to the pool when the part's holds drop.
        self._part_leases: Dict[int, staging_pool.StagingLease] = {}
        self._transfers_remaining = 0
        self._crc_ok = self._crc is None
        self._completed = False
        self._failed = False

    def set_device_cost_releaser(self, release: Callable[[int], None]) -> None:
        self._device_release = release

    def note_device_cost(self, nbytes: int) -> None:
        with self._lock:
            self._deposited += nbytes

    def extra_first_cost_bytes(self) -> int:
        # No host assembly buffer: parts go straight to device. Charging
        # the whole object on the first sub-read would serialize
        # concurrent large streaming restores under a tight budget —
        # defeating the read/H2D overlap this class exists for.
        return 0

    def deferred_cost_bytes(self, first: bool, part_nbytes: int) -> int:
        # Every part's payload outlives its consume: the overlap engine
        # holds it until the transfer completes, and (with an
        # incremental crc) the out-of-order stash may hold it until the
        # prefix drains. Released per-part once both holds drop.
        return part_nbytes

    def add_sub_reads(self, path: str, part_size: int) -> List[ReadReq]:
        reqs = super().add_sub_reads(path, part_size)
        self._transfers_remaining = len(reqs)
        return reqs

    def _release_assembly_cost(self) -> None:
        # Error-path safety net: re-credit whatever the per-part
        # refcounts have not already released (on success they cover the
        # whole object and this is a no-op).
        release, self._cost_release = self._cost_release, None
        if release is not None:
            with self._lock:
                remaining = self.nbytes - self._released
                self._released = self.nbytes
            if remaining > 0:
                release(remaining)

    def reads_into_pool(self, nbytes: int) -> bool:
        # A part's put must not alias its buffer, which is refilled
        # once the part is released.
        return h2d_put_copies(nbytes, self._device)

    def hold_part_lease(
        self, start: int, lease: staging_pool.StagingLease
    ) -> None:
        with self._lock:
            self._part_leases[start] = lease

    def _give_back_part(self, start: int) -> None:
        with self._lock:
            lease = self._part_leases.pop(start, None)
        if lease is not None:
            lease.release()

    def _part_release(self, start: int, nbytes: int) -> None:
        release = None
        with self._lock:
            refs = self._part_refs.get(start)
            if refs is None:
                return
            refs -= 1
            if refs:
                self._part_refs[start] = refs
                return
            del self._part_refs[start]
            release = self._cost_release
            if release is not None:
                self._released += nbytes
        # The buffer first: a read that the budget's credit admits finds
        # it free.
        self._give_back_part(start)
        if release is not None:
            release(nbytes)

    def _transfer_done(self, start: int, nbytes: int, fut: Any) -> None:
        if fut.cancelled() or fut.exception() is not None:
            # The restore is failing: finalize (or the plan's safety
            # net) re-raises the registered future's error before
            # anything is published. Mark failed so on_done never fires
            # over a partial deposit — and release the stream's
            # remaining deferred-budget holds NOW, so the doomed
            # restore's other reads don't crawl through forced
            # admission against a starved budget until the finalizer
            # surfaces the error. The transfer no longer reads the
            # part: its hold drops as on success, which gives the
            # buffer back once the fold is done with it too.
            with self._lock:
                self._failed = True
            self._part_release(start, nbytes)
            self._release_assembly_cost()
            return
        # Deposit straight into the region, keyed by region-flat byte
        # offset (distinct keys across all of the region's chunk
        # streams; GIL-atomic dict write). The chunks stay unreachable
        # to the application until the plan's finalize assembles them —
        # which only runs after every chunk's crc verified.
        self._region.device_chunks[self._flat_base + start] = fut.result()
        with self._lock:
            self._transfers_remaining -= 1
        self._part_release(start, nbytes)
        self._maybe_complete()

    def _maybe_complete(self) -> None:
        with self._lock:
            if (
                self._completed
                or self._failed
                or not self._crc_ok
                or self._remaining != 0
                or self._transfers_remaining != 0
            ):
                return
            self._completed = True
            # Hand the scheduler's device-budget reservation to the
            # region: finalize releases it once the concat frees the
            # per-chunk arrays.
            if self._device_release is not None and self._deposited:
                self._region.device_releases.append(
                    (self._device_release, self._deposited)
                )
                self._device_release = None
        try:
            self._on_done()
        finally:
            self._release_assembly_cost()

    async def absorb(
        self,
        start: int,
        end: int,
        buf: BufferType,
        executor: Optional[Executor] = None,
    ) -> None:
        def _consume_part() -> None:
            profile = self._profile
            with _cprof.consume_section():
                with _cprof.substep(profile, "view", len(buf)):
                    if len(buf) != end - start:
                        self._give_back_part(start)
                        raise RuntimeError(
                            f"Ranged sub-read returned {len(buf)} bytes for "
                            f"[{start}, {end}) — object shorter than the "
                            f"manifest implies (truncated or torn)."
                        )
                    flat = np.frombuffer(buf, dtype=self._np_dtype)
                    with self._lock:
                        self._part_refs[start] = (
                            2 if self._crc is not None else 1
                        )
                # Submit the H2D on the overlap engine FIRST: the
                # transfer rides the link while the crc fold below runs
                # on host and later sub-reads are still arriving.
                with _cprof.substep(profile, "h2d_submit", len(buf)):
                    fut = h2d_pipeline().submit(
                        flat, self._device, profile=profile
                    )
                    if self._register_transfer is not None:
                        self._register_transfer(fut)
                    fut.add_done_callback(
                        lambda f, s=start, n=len(buf): self._transfer_done(
                            s, n, f
                        )
                    )
                if self._crc is not None:
                    drained: List[Tuple[int, int]] = []
                    # The fold is in order and under the stream's fold
                    # lock: the wait for the lock (other parts of this
                    # object folding) is ``verify_wait``, the fold
                    # ``verify``.
                    with _cprof.substep(profile, "verify_wait", len(buf)):
                        self._fold_lock.acquire()
                    try:
                        with _cprof.substep(profile, "verify", len(buf)):
                            self._stash[start] = buf
                            while self._next_off in self._stash:
                                off = self._next_off
                                b = self._stash.pop(off)
                                self._crc.update(b)
                                self._next_off += len(b)
                                drained.append((off, len(b)))
                            stream_done = self._next_off >= self.nbytes
                    finally:
                        self._fold_lock.release()
                    # Re-credit drained parts outside the state lock
                    # (the budget cell takes its own lock).
                    for off, n in drained:
                        self._part_release(off, n)
                    if stream_done:
                        actual = self._crc.tag()
                        if actual != self._checksum:
                            with self._lock:
                                self._failed = True
                            raise RuntimeError(
                                f"Checksum mismatch: stored object is "
                                f"corrupt (expected {self._checksum}, "
                                f"got {actual})."
                            )
                        with self._lock:
                            self._crc_ok = True

        await _on_consume_executor(
            executor, _consume_part, self._profile, len(buf)
        )
        with self._lock:
            self._remaining -= 1
        # Inside the scheduler's consume span, on the event loop: a
        # finalize that this completion triggers (the transfers were all
        # done) is the consume wall's own, not overlapped work.
        with _cprof.consume_section():
            self._maybe_complete()


class _SubRangeConsumer(BufferConsumer):
    """One ranged sub-read of a split whole-object read."""

    def __init__(
        self, state: _SplitObjectReadState, start: int, end: int, first: bool
    ) -> None:
        self._state = state
        self._start = start
        self._end = end
        self._first = first

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        await self._state.absorb(self._start, self._end, buf, executor)

    def get_consuming_cost_bytes(self) -> int:
        # Each sub-read charges its own payload; the first additionally
        # carries the state's shared-allocation cost (the host assembly
        # buffer — zero for streaming states, which have none). The
        # scheduler dispatches reads in list order, so the first is
        # admitted before the others. The inner consumer's view is
        # zero-copy over the assembly buffer, so its cost is not
        # double-charged.
        extra = self._state.extra_first_cost_bytes() if self._first else 0
        return (self._end - self._start) + extra

    def get_deferred_cost_bytes(self) -> int:
        # The deferred portion's allocation outlives this consume (the
        # assembly buffer until the LAST sub-read; a streamed part's
        # stash entry until the crc prefix drains), so its reservation is
        # released through the scheduler's callback when actually freed,
        # not at consume completion.
        return self._state.deferred_cost_bytes(
            self._first, self._end - self._start
        )

    def set_cost_releaser(self, release: Callable[[int], None]) -> None:
        self._state.set_cost_releaser(release)

    def reads_into_pool(self) -> bool:
        # Streamed parts: each is let go once it has landed and been
        # folded into its object's checksum.
        return isinstance(
            self._state, _StreamingSplitState
        ) and self._state.reads_into_pool(self._end - self._start)

    def hold_read_lease(self, lease: Any) -> None:
        self._state.hold_part_lease(self._start, lease)

    @property
    def sort_key_bytes(self) -> int:
        # Scheduler dispatch ordering: all of one object's sub-reads
        # share the object's size, keeping the group contiguous under
        # the largest-first stable sort (the first sub-read's consuming
        # COST carries the assembly surcharge and must not be used as
        # the ordering key).
        return self._state.nbytes

    def get_device_cost_bytes(self) -> int:
        # Streaming sub-reads put their payload in device memory the
        # moment they consume, and it stays there until the REGION
        # assembles — so the whole region is charged up front by its
        # first admitted sub-read (SURVEY §7 hard-part 5: the scheduler
        # gates consume dispatch on a device-side budget; per-part
        # charges could not hold it, since releases only arrive at
        # region finalize). The charge is TWICE the region: deposited
        # chunks + the concatenated result coexist during assembly, and
        # after it the restored array stays RESIDENT — finalize releases
        # only the transient half, so the budget keeps tracking
        # cumulative HBM the restore now occupies (r5 review finding:
        # recrediting the full region let admissions run ~2x past the
        # free-HBM snapshot the budget came from). Sub-reads of an
        # already-charged region cost 0 — completing a started region is
        # always admissible, which is the progress property the
        # pipeline needs.
        if not isinstance(self._state, _StreamingSplitState):
            return 0
        region = self._state._region
        return 0 if region.device_charged else 2 * region.nbytes

    def set_device_cost_releaser(
        self, release: Callable[[int], None]
    ) -> None:
        region = self._state._region
        region.device_charged = True
        self._state.set_device_cost_releaser(release)
        # The transient half, returned by finalize once the concat's
        # buffers settle; the resident half stays charged.
        self._state.note_device_cost(region.nbytes)


class _ContentChunksReadState(_PooledAssemblyState):
    """Reassembles the content-addressed chunks of ONE stored object
    (chunkstore.py manifest entries) into its logical payload, then
    runs the real consumer on the whole payload — the chunk-store
    mirror of :class:`_SplitObjectReadState`, with per-chunk codec
    decode and content verification fused into the consume executor so
    decodes overlap reads still in flight.

    Integrity per chunk, independent of which take wrote it:
    losslessly-coded chunks must fingerprint back to the content key
    (xs128 of the logical bytes — stronger than a crc, and available
    even for chunks this manifest only references); lossy (int8)
    chunks verify their self-checking frame. Stored-size and stored-crc
    checks additionally apply where this manifest recorded them (the
    chunks its own take wrote)."""

    def __init__(
        self,
        inner: BufferConsumer,
        records: List[Dict[str, Any]],
        dtype_name: str,
        store_base: Optional[int],
        selected: Optional[List[int]] = None,
    ) -> None:
        super().__init__(sum(int(r["n"]) for r in records))
        self._inner = inner
        self._records = records
        self._dtype_name = dtype_name
        self._store_base = store_base
        # Chunk pushdown (snapfleet): when set, only these record
        # indices are fetched — the rest of the assembly buffer stays
        # unwritten, which is safe because the scatter only ever reads
        # the slice boxes whose byte hulls selected these records
        # (pushdown.select_records). Offsets stay the ORIGINAL
        # cumulative offsets so selected bytes land where the scatter
        # expects them.
        self._selected = (
            list(range(len(records))) if selected is None else selected
        )
        self._remaining = len(self._selected)

    def build_reads(self) -> List[ReadReq]:
        from .chunkstore import chunk_object_path
        from .storage_plugin import make_ref_location

        offsets = [0]
        for rec in self._records:
            offsets.append(offsets[-1] + int(rec["n"]))
        reqs: List[ReadReq] = []
        for j, i in enumerate(self._selected):
            rec = self._records[i]
            path = chunk_object_path(rec["k"])
            if self._store_base is not None:
                path = make_ref_location(self._store_base, path)
            reqs.append(
                ReadReq(
                    path=path,
                    buffer_consumer=_ContentChunkConsumer(
                        self, rec, offsets[i], first=(j == 0)
                    ),
                )
            )
        return reqs

    async def absorb(
        self,
        rec: Dict[str, Any],
        offset: int,
        buf: BufferType,
        executor: Optional[Executor] = None,
    ) -> None:
        def _consume_part() -> None:
            from .chunkstore import decode_and_verify_chunk

            with _cprof.consume_section():
                self._ensure_buf()
                n = int(rec["n"])
                # Disjoint offsets: concurrent executor threads never
                # overlap. Identity-coded chunks decode ZERO-COPY
                # straight into the pooled assembly buffer (one verify
                # + one memcpy); codec chunks decode to a transient
                # then splice.
                out = memoryview(self._buf)[offset : offset + n]
                logical = decode_and_verify_chunk(
                    rec,
                    self._dtype_name,
                    buf,
                    profile=self._profile,
                    out=out,
                )
                if logical is not None:
                    with _cprof.substep(
                        self._profile, "reassemble", len(logical)
                    ):
                        out[: len(logical)] = logical

        await _on_consume_executor(
            executor, _consume_part, self._profile, len(buf)
        )
        with self._lock:
            self._remaining -= 1
            last = self._remaining == 0
        if last:
            try:
                await self._inner.consume_buffer(
                    memoryview(self._buf)[: self.nbytes], executor
                )
            finally:
                with _cprof.consume_section(), _cprof.substep(
                    self._profile, "staging_release", self.nbytes
                ):
                    # Pool return fires the scheduler budget re-credit
                    # exactly once, however many chunks shared it.
                    self._release_assembly_buffer()


class _ContentChunkConsumer(BufferConsumer):
    """One content chunk of a chunk-stored object."""

    def __init__(
        self,
        state: _ContentChunksReadState,
        rec: Dict[str, Any],
        offset: int,
        first: bool,
    ) -> None:
        self._state = state
        self._rec = rec
        self._offset = offset
        self._first = first

    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        await self._state.absorb(self._rec, self._offset, buf, executor)

    def _part_cost(self) -> int:
        # Stored bytes held during the read + the decoded transient.
        rec = self._rec
        return int(rec.get("sn") or rec["n"]) + int(rec["n"])

    def get_consuming_cost_bytes(self) -> int:
        # The first chunk additionally carries the shared assembly
        # buffer (released when the LAST chunk lands) — the same
        # charging discipline as split whole-object reads.
        return self._part_cost() + (self._state.nbytes if self._first else 0)

    def get_deferred_cost_bytes(self) -> int:
        return self._state.nbytes if self._first else 0

    def set_cost_releaser(self, release: Callable[[int], None]) -> None:
        self._state.set_cost_releaser(release)

    @property
    def sort_key_bytes(self) -> int:
        # All of one object's chunk reads share the object's logical
        # size so the largest-first stable sort keeps the group
        # contiguous (same convention as split sub-reads).
        return self._state.nbytes


class ArrayRestorePlan:
    """Plans and finalizes the restore of one array entry into a template.

    The template supplies the target placement: a ``jax.Array`` template's
    sharding decides which global regions land on which local devices; a
    numpy/None template restores the full array on host.
    """

    def __init__(self, entry: Entry, template: Any, callback: Callable[[Any], None]):
        # Tuple tail: the stored object's own ArrayEntry — needed by the
        # content-chunk branch (chunkstore.py entries read per content
        # chunk instead of per stored object).
        if isinstance(entry, ShardedArrayEntry):
            dtype_name, shape = entry.dtype, list(entry.shape)
            chunks = [
                (
                    list(s.offsets),
                    list(s.sizes),
                    s.array.location,
                    s.array.checksum,
                    s.array.compression,
                    s.array,
                )
                for s in entry.shards
            ]
        elif isinstance(entry, ArrayEntry):
            dtype_name, shape = entry.dtype, list(entry.shape)
            chunks = [
                (
                    [0] * len(shape),
                    list(shape),
                    entry.location,
                    entry.checksum,
                    entry.compression,
                    entry,
                )
            ]
        else:
            raise TypeError(f"Not an array entry: {type(entry)}")
        self._entry = entry
        self._callback = callback
        self._dtype = str_to_dtype(dtype_name)
        self._shape = shape
        self._prng_impl = getattr(entry, "prng_impl", None)
        # Plan-build runs in the restoring thread, under the restore's
        # trace scope; finalize may instead run on the finalize pool or
        # an engine done-callback thread, whose fresh contexts would
        # attribute the assemble span to no trace. Capture now, adopt
        # in _finalize_now.
        self._trace_id = tracing.current_trace_id()

        if (
            self._prng_impl is not None
            and _is_jax_array(template)
            and _is_prng_key_array(template)
        ):
            # Saved payload is uint32 key data (trailing impl dim). The key
            # data view shares the keys' device layout, so use it as the
            # placement template and re-wrap after assembly.
            template = jax.random.key_data(template)
        placement = template_placement(template)
        self._template_is_jax = placement is not None
        self._sharding = None
        regions: Dict[Tuple[Tuple[int, ...], Tuple[int, ...]], _TargetRegion] = {}
        if placement is not None:
            if list(template.shape) != shape:
                raise RuntimeError(
                    f"Cannot restore array of shape {shape} into a template "
                    f"of shape {list(template.shape)}. Shapes must match; "
                    f"resharding (different mesh/partitioning) is supported, "
                    f"reshaping is not."
                )
            # The template is read here and never again: its sharding
            # and where its shards lie. The plan keeps no reference to
            # it, so whoever owns it may release its buffers before the
            # replacement lands (snapshot._load_stateful).
            self._sharding, shards = placement
            for device, index in shards:
                off, sz = index_to_offsets_sizes(index, shape)
                key = (tuple(off), tuple(sz))
                if key not in regions:
                    # Device-template region buffers are pool-backed:
                    # device_put copies out of them, so the backing can
                    # be donated back to the pool at finalize. Host
                    # templates hand the buffer to the app — never
                    # pooled.
                    regions[key] = _TargetRegion(
                        off, sz, self._dtype, poolable=True
                    )
                regions[key].devices.append(device)
        else:
            if template is not None and hasattr(template, "shape"):
                if list(template.shape) != shape and self._prng_impl is None:
                    raise RuntimeError(
                        f"Cannot restore array of shape {shape} into a template "
                        f"of shape {list(template.shape)}."
                    )
            off = [0] * len(shape)
            regions[(tuple(off), tuple(shape))] = _TargetRegion(off, shape, self._dtype)
        self._regions = list(regions.values())
        # Host-backed (CPU) devices can ALIAS a device_put numpy buffer
        # instead of copying it — donating such a region's pooled
        # backing would let a later restore overwrite the "restored"
        # array through the alias. Pool region buffers only when every
        # consumer device's put copies them: across a link, or through
        # the chunked path (the test the early put and finalize choose
        # their put by).
        for region in self._regions:
            if not all(
                h2d_put_copies(region.nbytes, d) for d in region.devices
            ):
                region._poolable = False
        self._chunks = chunks
        # Eager-finalize bookkeeping: the last chunk consumer to complete
        # triggers finalize() from its executor thread (or the overlap
        # engine's done-callback thread), so host→device assembly of
        # this array overlaps with other arrays' reads.
        self._outstanding = 0
        self._finalized = False
        self._lock = threading.Lock()
        self._profile = _cprof.current()
        # Overlap-engine bookkeeping: every engine future (streamed
        # chunks + early region puts) is registered here so finalize
        # surfaces transfer failures before publishing, and the
        # completion event closes the tiny future-resolved→callback-ran
        # window for the safety-net finalizer.
        self._transfers: List[Any] = []
        self._complete = threading.Event()
        self._finalize_done = threading.Event()
        self._finalize_error: Optional[BaseException] = None

    def _register_transfer(self, fut: Any) -> None:
        with self._lock:
            self._transfers.append(fut)

    def _on_req_done(self) -> None:
        with self._lock:
            self._outstanding -= 1
            if self._outstanding != 0:
                return
        self._complete.set()
        self.finalize()

    def _note_region_copy(self, region: _TargetRegion) -> None:
        """A chunk-copy consumer finished scattering into ``region``.
        When the region's last copy lands — and it is a single-device,
        engine-worthy region — dispatch its H2D on the overlap engine
        NOW instead of at plan finalize, so transfers of completed
        regions overlap chunks still reading/decoding."""
        with self._lock:
            region.pending_copies -= 1
            ready = region.pending_copies == 0
        if (
            not ready
            or not self._template_is_jax
            or region.device_chunks is not None
            or len(region.devices) != 1
            or region.buffer is None
            or region.nbytes < 2 * h2d_chunk_bytes()
        ):
            return
        fut = h2d_pipeline().submit(
            region.buffer, region.devices[0], profile=self._profile
        )
        region.early_put = fut
        self._register_transfer(fut)
        fut.add_done_callback(
            lambda f, region=region: self._early_put_done(region, f)
        )

    def _early_put_done(self, region: _TargetRegion, fut: Any) -> None:
        # The engine block_until_ready'd the transfer (or it failed):
        # either way the pooled backing is no longer read — donate it
        # back promptly so concurrent restores stop waiting on pool
        # capacity. The device array lives in the future for finalize.
        region.release_lease()

    def build_read_reqs(self) -> List[ReadReq]:
        reqs: List[ReadReq] = []
        n_logical = 0  # finalize triggers: one per chunk consumed
        split_threshold = _parallel_read_threshold()
        itemsize = np.dtype(self._dtype).itemsize
        strict = os.environ.get("TPUSNAPSHOT_STRICT_INTEGRITY") == "1"

        # Pass 1: overlaps of every chunk against every region.
        planned = []  # (chunk fields..., copies)
        for chunk_off, chunk_sz, location, chunk_checksum, compression, aentry in self._chunks:
            copies: List[Tuple[_TargetRegion, Tuple[slice, ...], Overlap]] = []
            for region in self._regions:
                ov = compute_overlap(chunk_off, chunk_sz, region.offsets, region.sizes)
                if ov is not None:
                    copies.append((region, ov.target_slices, ov))
            if copies:
                planned.append(
                    (chunk_off, chunk_sz, location, chunk_checksum,
                     compression, aentry, copies)
                )

        # Pass 2: pick the regions whose ENTIRE payload can stream to
        # device as it lands (VERDICT r4 #2: streaming used to engage
        # only when one object exactly covered one region; with
        # format-chunked dense arrays the dominant shape is SEVERAL
        # whole chunks tiling one single-device region, each chunk a
        # contiguous byte run of the region's flat layout). Streaming is
        # all-or-nothing per region — mixing streamed chunks with
        # host-buffer chunks would need a partial host buffer AND a
        # device concat for the same region.
        stream_region: Dict[int, Dict[int, int]] = {}  # id(region) -> {id(ov): flat_base}
        by_region: Dict[int, List] = {}
        for item in planned:
            for region, _, ov in item[6]:
                by_region.setdefault(id(region), []).append((item, ov))
        region_by_id = {id(r): r for r in self._regions}
        for rid, items in by_region.items():
            region = region_by_id[rid]
            if not (self._template_is_jax and len(region.devices) == 1):
                continue
            total = sum(
                _chunk_nbytes(it[1], itemsize) for it, _ in items
            )
            if total <= split_threshold:
                # Small regions keep the batched-device_put path: one
                # put per tiny shard beats many micro-streams.
                continue
            flat_bases: Dict[int, int] = {}
            ok = True
            for (chunk_off, chunk_sz, _, _, compression, aentry,
                 copies), ov in items:
                run = contiguous_byte_range(
                    region.sizes, ov.target_slices, itemsize
                )
                if (
                    compression is not None
                    # Content-chunked stored objects (chunkstore.py)
                    # assemble from per-chunk decodes on host — they
                    # cannot stream raw byte ranges to device.
                    or getattr(aentry, "chunks", None)
                    or len(copies) != 1
                    or run is None
                    or any(
                        sl.start != 0 or sl.stop != dim
                        for sl, dim in zip(ov.chunk_slices, chunk_sz)
                    )
                ):
                    ok = False
                    break
                flat_bases[id(ov)] = run[0]
            if ok:
                stream_region[rid] = flat_bases
                # The host-side region buffer is never touched on this
                # path (and, being lazy, was never allocated); the
                # device-chunk dict marks the region as streaming.
                region.device_chunks = {}

        # Pass 3: emit read requests. Adopting a zero-copy view is only
        # safe when the payload handed to the consumer is NOT a pooled
        # assembly buffer (the view would pin pool memory past its
        # release); direct read payloads always qualify.
        adopt_from_state_ok = staging_pool.get_staging_pool() is None
        for (chunk_off, chunk_sz, location, chunk_checksum, compression,
             aentry, copies) in planned:
            chunk_nbytes = _chunk_nbytes(chunk_sz, itemsize)
            content = getattr(aentry, "chunks", None)
            if content:
                # Content-chunked stored object (chunkstore.py): one
                # read per content chunk, each decoded (codec) and
                # content-verified in the consume executor — decode
                # overlaps the remaining reads — then scattered into
                # the overlapping regions exactly like a whole-object
                # read would be.
                for region, _rs, _ov in copies:
                    region.pending_copies += 1
                inner = _ChunkCopyConsumer(
                    view_shape=list(chunk_sz),
                    dtype=self._dtype,
                    copies=[
                        (region, region_slices, ov.chunk_slices)
                        for region, region_slices, ov in copies
                    ],
                    on_done=self._on_req_done,
                    allow_adopt=adopt_from_state_ok,
                    region_notify=self._note_region_copy,
                )
                n_logical += 1
                # Chunk pushdown: when this process's target slices
                # cover only part of the stored object (a differently-
                # meshed restore), cut the record list to those whose
                # byte ranges intersect the slices' C-order byte hulls
                # — each client fetches ≈ its shard fraction instead of
                # the whole object. Conservative (hull ⊇ strided
                # footprint) and disabled under strict integrity (the
                # skipped records can't be verified if never read).
                selected = None
                if (
                    not strict
                    and os.environ.get("TPUSNAPSHOT_CHUNK_PUSHDOWN")
                    != "0"
                ):
                    from .snapserve import pushdown

                    sizes = [int(r["n"]) for r in content]
                    sel = pushdown.select_records(
                        sizes,
                        pushdown.needed_intervals(
                            tuple(chunk_sz),
                            [
                                tuple(
                                    (sl.start, sl.stop)
                                    for sl in ov.chunk_slices
                                )
                                for _r, _rs, ov in copies
                            ],
                            itemsize,
                        ),
                    )
                    if 0 < len(sel.indices) < len(content):
                        selected = sel.indices
                        telemetry.counter(
                            _metric_names.CHUNK_PUSHDOWN_SKIPPED_BYTES
                        ).inc(sum(sizes) - sel.selected_bytes)
                state = _ContentChunksReadState(
                    inner,
                    content,
                    dtype_name=aentry.dtype,
                    store_base=getattr(aentry, "base", None),
                    selected=selected,
                )
                reqs.extend(state.build_reads())
                continue
            # Sub-range boundaries must land on element boundaries for
            # streaming device chunks.
            part = max(
                itemsize, split_threshold - (split_threshold % itemsize)
            )
            if (
                len(copies) == 1
                and id(copies[0][0]) in stream_region
            ):
                # Whole chunk streams into its region at its flat
                # offset, overlapping storage reads with H2D transfers.
                # The crc verifies incrementally over the in-order byte
                # stream — valid under TPUSNAPSHOT_STRICT_INTEGRITY.
                region0, _, ov0 = copies[0]
                stream = _StreamingSplitState(
                    chunk_nbytes,
                    region=region0,
                    dtype=np.dtype(self._dtype),
                    checksum=chunk_checksum,
                    on_done=self._on_req_done,
                    flat_base=stream_region[id(region0)][id(ov0)],
                    register_transfer=self._register_transfer,
                )
                n_logical += 1
                reqs.extend(stream.add_sub_reads(location, part))
                continue
            ranges = [
                contiguous_byte_range(chunk_sz, ov.chunk_slices, itemsize)
                for _, _, ov in copies
            ]
            partial = len(copies) > 1 or (
                ranges[0] is not None and (ranges[0][1] - ranges[0][0]) < chunk_nbytes
            )
            # Compressed chunks admit no ranged reads (byte offsets into the
            # compressed stream are meaningless): always read whole. Ranged
            # reads also cannot verify the chunk's checksum (it covers the
            # whole stored object) — TPUSNAPSHOT_STRICT_INTEGRITY=1 trades
            # the ranged-read bandwidth savings for full verification.
            if (
                compression is None
                and not strict
                and all(r is not None for r in ranges)
                and partial
            ):
                # Every overlap is a contiguous byte run of the chunk: issue
                # one ranged read per target region (parallel, and each
                # process/device fetches only the bytes it needs).
                for (region, region_slices, ov), rng in zip(copies, ranges):
                    full = tuple(slice(0, s) for s in ov.sizes)
                    sub_nbytes = rng[1] - rng[0]
                    split = sub_nbytes > split_threshold
                    region.pending_copies += 1
                    consumer = _ChunkCopyConsumer(
                        view_shape=list(ov.sizes),
                        dtype=self._dtype,
                        copies=[(region, region_slices, full)],
                        on_done=self._on_req_done,
                        # Split payloads arrive as pooled assembly
                        # views; direct ranged payloads may adopt.
                        allow_adopt=(not split) or adopt_from_state_ok,
                        region_notify=self._note_region_copy,
                    )
                    n_logical += 1
                    if split:
                        # A large contiguous sub-range is still one
                        # stream: split it the same way as whole objects
                        # (offsets shifted by the range start).
                        state = _SplitObjectReadState(sub_nbytes, consumer)
                        for sub in state.add_sub_reads(
                            location, split_threshold
                        ):
                            sub.byte_range = (
                                rng[0] + sub.byte_range[0],
                                rng[0] + sub.byte_range[1],
                            )
                            reqs.append(sub)
                    else:
                        reqs.append(
                            ReadReq(
                                path=location,
                                buffer_consumer=consumer,
                                byte_range=rng,
                            )
                        )
            else:
                # Non-contiguous overlap somewhere: read the chunk once and
                # scatter into every overlapping region. Whole-object reads
                # can verify the stored checksum (ranged reads cannot).
                def _whole_consumer(
                    allow_adopt: bool = True,
                    whole_object: Optional[str] = None,
                ):
                    for region, _rs, _ov in copies:
                        region.pending_copies += 1
                    return _ChunkCopyConsumer(
                        view_shape=list(chunk_sz),
                        dtype=self._dtype,
                        copies=[
                            (region, region_slices, ov.chunk_slices)
                            for region, region_slices, ov in copies
                        ],
                        checksum=chunk_checksum,
                        compression=compression,
                        on_done=self._on_req_done,
                        allow_adopt=allow_adopt,
                        region_notify=self._note_region_copy,
                        whole_object=whole_object,
                    )

                n_logical += 1
                if compression is None and chunk_nbytes > split_threshold:
                    # Large whole-object read → concurrent ranged
                    # sub-reads; the checksum is verified over the
                    # assembled payload, so this stays valid under
                    # TPUSNAPSHOT_STRICT_INTEGRITY. (Compressed objects
                    # can't split: their stored size is not derivable
                    # from the manifest shape. Streaming-to-device was
                    # decided per-REGION in pass 2; chunks landing here
                    # reassemble on host.)
                    state = _SplitObjectReadState(
                        chunk_nbytes, _whole_consumer(adopt_from_state_ok)
                    )
                    reqs.extend(state.add_sub_reads(location, part))
                else:
                    reqs.append(
                        ReadReq(
                            path=location,
                            buffer_consumer=_whole_consumer(
                                whole_object=location
                            ),
                        )
                    )
        with self._lock:
            # One finalize trigger per logical chunk (a split chunk's
            # inner consumer fires on_done once, not once per sub-read).
            self._outstanding = n_logical
        if n_logical == 0:
            self._complete.set()
        return reqs

    def finalize(self) -> None:
        # Normally triggered eagerly by the last chunk consumer (or the
        # overlap engine's last done-callback); the finalizer returned
        # by prepare_read is the safety net for plans with zero read
        # requests — and, post-fastlane, the thread that surfaces a
        # failed overlap-engine transfer. The latch is BLOCKING, not
        # merely idempotent: an eager finalize may be mid-assembly on
        # an engine thread the scheduler never awaited, so a losing
        # caller must wait for publication (and re-raise the winner's
        # failure) before the restore continues past its finalizers.
        run = False
        with self._lock:
            if not self._finalized:
                self._finalized = True
                run = True
        if not run:
            self._finalize_done.wait()
            err = self._finalize_error
            if err is not None:
                raise err
            return
        if _on_h2d_engine_thread():
            # Never block an engine worker in _await_pipeline: it may
            # be the only worker able to run the futures being awaited
            # (deadlock at depth 1). Hop to the finalize pool; waiters
            # block on _finalize_done as usual and re-raise any error.
            _get_finalize_pool().submit(self._finalize_now)
            return
        self._finalize_now()

    def _finalize_now(self) -> None:
        try:
            # Blocks until the overlap engine has landed this leaf's
            # transfers: inside a consume when the leaf's last consume
            # triggered the finalize.
            with _cprof.substep(self._profile, "h2d_wait"):
                self._await_pipeline()
            with tracing.adopt_trace(self._trace_id), tracing.span(
                "assemble"
            ):
                self._finalize_impl()
        except BaseException as e:  # noqa: BLE001 — SimulatedCrash must surface
            # When this runs on the finalize pool the raise lands in an
            # unobserved future; the error still reaches the restore
            # thread via _finalize_error at the safety-net finalizer.
            self._finalize_error = e
            raise
        finally:
            self._finalize_done.set()

    def _await_pipeline(self) -> None:
        """Wait out (and surface errors from) every overlap-engine
        transfer this plan dispatched, BEFORE anything is published. A
        transfer failure (including faultline's SimulatedCrash) or an
        incomplete pipeline raises here — the restore fails with the
        template untouched, never with a torn leaf."""
        with self._lock:
            transfers = list(self._transfers)
        waited = len(transfers)
        while transfers:
            transfers.pop().result()  # re-raises transfer errors
        # A resolved future holds what it returned: a streamed part's
        # device chunk. Let go of those waited for, or every chunk
        # outlives its region's assembly for as long as this plan does
        # (it sits in reference cycles, so until a cyclic collection),
        # and a state above half of HBM is on the device nearly twice
        # while the device budget counts the chunks as freed.
        with self._lock:
            del self._transfers[:waited]
            outstanding = self._outstanding
        if outstanding == 0:
            return
        # All registered futures resolved; the only legitimate gap is a
        # done-callback still running on another thread. Anything past
        # a generous wait is a pipeline bug — refuse to assemble.
        if not self._complete.wait(timeout=60.0):
            raise RuntimeError(
                f"streaming restore pipeline incomplete: "
                f"{outstanding} chunk(s) never finished "
                f"decode/verify/transfer — refusing to publish a torn "
                f"leaf"
            )

    def _finalize_impl(self) -> None:
        if self._template_is_jax:
            # Streamed regions (device_chunks set) noted their H2D as
            # per-chunk h2d_overlap on the engine, and early-dispatched
            # regions (early_put set) likewise — counting them again
            # here would double the profile's transfer bytes. Only
            # regions still placed from host buffers at finalize
            # transfer bytes now.
            with _cprof.substep(
                self._profile,
                "device_put",
                sum(
                    r.nbytes * max(1, len(r.devices))
                    for r in self._regions
                    if r.device_chunks is None and r.early_put is None
                ),
            ):
                self._finalize_jax()
            return
        out = self._regions[0].ensure_buffer(self._profile)
        if not out.flags.writeable:
            # Adopted zero-copy payload views are read-only; host
            # restores hand back writable arrays (apps mutate restored
            # numpy state in place).
            out = out.copy()
        if self._prng_impl is not None:
            out = jax.random.wrap_key_data(out, impl=self._prng_impl)
        self._callback(out)

    def _finalize_jax(self) -> None:
        # One batched device_put for all shards: the runtime issues the
        # host→device transfers in parallel (a serial per-shard loop is
        # memcpy/PCIe-latency bound). Large buffers route through the
        # chunked H2D path instead — a single big transfer leaves
        # ~40% of the measured link bandwidth on the table
        # (ops/transfer.py chunked_device_put).
        buffers = []
        devices = []
        prebuilt: Dict[int, Any] = {}
        lease_slots: List[Tuple[_TargetRegion, int]] = []
        for region in self._regions:
            for device in region.devices:
                if region.early_put is not None:
                    # The overlap engine already placed this region
                    # (chunk-granular overlap: dispatched the moment its
                    # last copy landed); the future is resolved — errors
                    # were surfaced by _await_pipeline — and the pooled
                    # backing was donated back in the done-callback.
                    prebuilt[len(buffers)] = region.early_put.result()
                    buffers.append(None)
                    devices.append(device)
                    continue
                if region.device_chunks is not None:
                    # Streaming reads: the bytes are already on
                    # device as 1-D chunks keyed by flat offset —
                    # concatenate in offset order + reshape there
                    # instead of a host device_put.
                    ordered = [
                        region.device_chunks[k]
                        for k in sorted(region.device_chunks)
                    ]
                    flat = (
                        jnp.concatenate(ordered)
                        if len(ordered) > 1
                        else ordered[0]
                    )
                    assembled = jnp.reshape(flat, tuple(region.sizes))
                    prebuilt[len(buffers)] = assembled
                    # Free the per-chunk arrays eagerly and return
                    # the TRANSIENT half of the device reservation
                    # (the assembled array's half stays charged — it
                    # remains resident). Wait for the concat to
                    # actually execute first: releasing at dispatch
                    # time would re-admit new streams while chunks
                    # and result still coexist.
                    region.device_chunks = None
                    del flat, ordered
                    if region.device_releases:
                        try:
                            assembled.block_until_ready()
                        # Only times the budget release; a real
                        # failure re-raises at device_put below.
                        except Exception:  # snapcheck: disable=swallowed-exception -- timing wait
                            pass
                        releases, region.device_releases = (
                            region.device_releases,
                            [],
                        )
                        for cb, nbytes in releases:
                            cb(nbytes)
                if region._lease is not None:
                    lease_slots.append((region, len(buffers)))
                buffers.append(region.buffer)
                devices.append(device)
        chunk_mask = [
            False
            if i in prebuilt
            else should_chunk_h2d(buf, dev)
            for i, (buf, dev) in enumerate(zip(buffers, devices))
        ]
        arrays: List[Any] = [None] * len(buffers)
        for i, arr in prebuilt.items():
            arrays[i] = arr
        # Large buffers stream chunked; the small remainder still
        # goes in ONE batched device_put (a per-buffer loop over
        # many small shards is exactly the latency-bound path the
        # batching exists to avoid).
        small = [
            i
            for i, chunked in enumerate(chunk_mask)
            if not chunked and i not in prebuilt
        ]
        if small:
            put = jax.device_put(
                [buffers[i] for i in small],
                [devices[i] for i in small],
            )
            for i, arr in zip(small, put):
                arrays[i] = arr
        for i, chunked in enumerate(chunk_mask):
            if chunked:
                arrays[i] = chunked_device_put(buffers[i], devices[i])
        out = jax.make_array_from_single_device_arrays(
            tuple(self._shape), self._sharding, arrays
        )
        if self._prng_impl is not None:
            out = jax.random.wrap_key_data(out, impl=self._prng_impl)
        self._callback(out)
        if lease_slots:
            # Batched donation: pooled region buffers return to the
            # pool in ONE pass — after the runtime finished reading
            # them (device_put can return before the copy-out), so a
            # reuse by a concurrent restore can never alias an
            # in-flight transfer. Publication (the callback above) was
            # not delayed by this wait.
            with _cprof.substep(
                self._profile,
                "staging_release",
                sum(r.nbytes for r, _ in lease_slots),
            ):
                try:
                    jax.block_until_ready(
                        [arrays[i] for _, i in lease_slots]
                    )
                except Exception:  # snapcheck: disable=swallowed-exception -- donation wait; a transfer failure keeps the lease unreleased (GC net)
                    return
                seen = set()
                for region, _ in lease_slots:
                    if id(region) not in seen:
                        seen.add(id(region))
                        region.release_lease()


def _chunk_nbytes(sizes: List[int], itemsize: int) -> int:
    n = itemsize
    for s in sizes:
        n *= s
    return n


def _prepare_dense_array_write(
    arr: Any,
    logical_path: str,
    rank: int,
    replicated: bool,
    compression: Optional[str] = None,
    eager_host_copy: bool = True,
) -> Tuple[Entry, List[WriteReq]]:
    prng_impl = None
    if _is_prng_key_array(arr):
        prng_impl = str(jax.random.key_impl(arr))
        arr = jax.random.key_data(arr)
    dtype = np.dtype(arr.dtype)
    dtype_name = dtype_to_str(arr.dtype)
    nbytes = _chunk_nbytes(list(arr.shape), dtype.itemsize)
    if nbytes > MAX_CHUNK_SIZE_BYTES:
        # Large dense arrays chunk at the FORMAT level into multiple
        # storage objects, exactly like sharded shards (VERDICT r4 #3:
        # a single multi-GiB object means single-stream writes and
        # full-buffer staging; split/streaming reads and GCS composite
        # uploads only papered over it per-backend). Reference analog:
        # the ≤512 MB shard subdivision at io_preparer.py:38,40-72 —
        # applied here to the dense path the reference never chunks.
        return _prepare_chunked_dense_write(
            arr,
            logical_path,
            rank,
            replicated,
            dtype,
            prng_impl,
            compression,
            eager_host_copy,
        )
    location = get_storage_path(rank, logical_path, replicated)
    entry = ArrayEntry(
        location=location,
        serializer=ARRAY_SERIALIZER,
        dtype=dtype_name,
        shape=list(arr.shape),
        replicated=replicated,
    )
    if prng_impl is not None:
        entry.prng_impl = prng_impl
    stager = ArrayBufferStager(
        arr, entry=entry, compression=compression, eager_host_copy=eager_host_copy
    )
    return entry, [WriteReq(path=location, buffer_stager=stager)]


def _prepare_chunked_dense_write(
    arr: Any,
    logical_path: str,
    rank: int,
    replicated: bool,
    dtype: np.dtype,
    prng_impl: Optional[str],
    compression: Optional[str],
    eager_host_copy: bool,
) -> Tuple[ShardedArrayEntry, List[WriteReq]]:
    """Plan a > ``MAX_CHUNK_SIZE_BYTES`` dense array as a chunked
    ``ShardedArrayEntry`` whose one-region shards are ordinary storage
    objects: staging holds chunk-sized host memory, writes fan out
    across the backend's concurrency, and restores split/stream without
    backend tricks. The entry's ownership category (``replicated`` /
    ``per_rank``) preserves the dense entry's elasticity semantics —
    chunk locations stay inside the owner's storage namespace
    (``<rank>/…`` / ``replicated/…``), so two ranks' same-named per-rank
    values can never collide on storage paths."""
    shape = list(arr.shape)
    # Chunk objects live under their own top-level namespace
    # ("chunked/<owner>/…"), disjoint from every dense leaf location
    # ("<rank>/…", "replicated/…") — a leaf literally named
    # "<path>_<offsets>" must never collide with a sibling's chunk
    # (r5 review finding). The ordinal suffix "__chunk_<i>" is
    # unambiguous by construction: every chunk location ends with it,
    # and stripping the final suffix recovers the logical path even
    # when another leaf's name embeds a chunk-like suffix.
    owner = "replicated" if replicated else str(rank)
    base = f"chunked/{owner}/{logical_path}"
    pieces = subdivide(
        [0] * len(shape), shape, dtype.itemsize, MAX_CHUNK_SIZE_BYTES
    )
    shards: List[Shard] = []
    reqs: List[WriteReq] = []
    for i, (c_off, c_sz) in enumerate(pieces):
        location = f"{base}__chunk_{i}"
        chunk_entry = ArrayEntry(
            location=location,
            serializer=ARRAY_SERIALIZER,
            dtype=dtype_to_str(arr.dtype),
            shape=list(c_sz),
            replicated=False,
        )
        shards.append(
            Shard(offsets=list(c_off), sizes=list(c_sz), array=chunk_entry)
        )
        local = tuple(slice(o, o + s) for o, s in zip(c_off, c_sz))
        stager = ArrayBufferStager(
            arr,
            chunk_slices=local,
            nbytes=_chunk_nbytes(c_sz, dtype.itemsize),
            entry=chunk_entry,
            compression=compression,
            eager_host_copy=eager_host_copy,
        )
        reqs.append(WriteReq(path=location, buffer_stager=stager))
    entry = ShardedArrayEntry(
        dtype=dtype_to_str(arr.dtype),
        shape=shape,
        shards=shards,
        prng_impl=prng_impl,
        replicated=replicated,
        per_rank=not replicated,
    )
    return entry, reqs


def _prepare_sharded_array_write(
    arr: jax.Array,
    logical_path: str,
    compression: Optional[str] = None,
    eager_host_copy: bool = True,
) -> Tuple[ShardedArrayEntry, List[WriteReq]]:
    prng_impl = None
    if _is_prng_key_array(arr):
        # Persist sharded key arrays through their uint32 key data, which
        # shares the keys' sharding (the trailing impl dim is unsharded).
        prng_impl = str(jax.random.key_impl(arr))
        arr = jax.random.key_data(arr)
    dtype = np.dtype(arr.dtype)
    dtype_name = dtype_to_str(dtype)
    global_shape = list(arr.shape)
    shards: List[Shard] = []
    reqs: List[WriteReq] = []
    for shard in arr.addressable_shards:
        if shard.replica_id != 0:
            continue  # exactly one process/device persists each region
        off, sz = index_to_offsets_sizes(shard.index, global_shape)
        pieces = subdivide(off, sz, dtype.itemsize, MAX_CHUNK_SIZE_BYTES)
        whole = len(pieces) == 1
        for c_off, c_sz in pieces:
            location = chunk_location(logical_path, c_off)
            entry = ArrayEntry(
                location=location,
                serializer=ARRAY_SERIALIZER,
                dtype=dtype_name,
                shape=list(c_sz),
                replicated=False,
            )
            shards.append(Shard(offsets=list(c_off), sizes=list(c_sz), array=entry))
            if whole:
                stager = ArrayBufferStager(
                    shard.data,
                    entry=entry,
                    compression=compression,
                    eager_host_copy=eager_host_copy,
                )
            else:
                local = tuple(
                    slice(co - o, co - o + cs) for co, cs, o in zip(c_off, c_sz, off)
                )
                stager = ArrayBufferStager(
                    shard.data,
                    chunk_slices=local,
                    nbytes=_chunk_nbytes(c_sz, dtype.itemsize),
                    entry=entry,
                    compression=compression,
                )
            reqs.append(WriteReq(path=location, buffer_stager=stager))
    return (
        ShardedArrayEntry(
            dtype=dtype_name,
            shape=global_shape,
            shards=shards,
            prng_impl=prng_impl,
        ),
        reqs,
    )


def prepare_write(
    obj: Any,
    logical_path: str,
    rank: int,
    replicated: bool = False,
    compression: Optional[str] = None,
    eager_host_copy: bool = True,
) -> Tuple[Entry, List[WriteReq]]:
    """Plan the persistence of one leaf value.

    Reference analog: io_preparer.py:345-374. Returns the manifest entry
    and the write requests this process is responsible for. For replicated
    values the caller (Snapshot) drops the write reqs on non-owner ranks.
    ``eager_host_copy=False`` (async takes) suppresses prepare-time
    device→host copy kickoff — a device-staged cut would never consume it.
    """
    # numpy scalars subclass Python numbers (np.float64 is a float), so the
    # array check must run before the primitive check.
    if isinstance(obj, (np.generic, np.ndarray)):
        return _prepare_dense_array_write(
            np.asarray(obj), logical_path, rank, replicated, compression
        )
    if isinstance(obj, _PRIMITIVE_TYPES):
        return PrimitiveEntry.from_value(obj, replicated=replicated), []
    if _is_jax_array(obj) and _is_partitioned(obj):
        return _prepare_sharded_array_write(
            obj, logical_path, compression, eager_host_copy
        )
    if _is_jax_array(obj):
        return _prepare_dense_array_write(
            obj, logical_path, rank, replicated, compression, eager_host_copy
        )
    location = get_storage_path(rank, logical_path, replicated)
    entry = ObjectEntry(
        location=location, serializer=OBJECT_SERIALIZER, replicated=replicated
    )
    stager = ObjectBufferStager(obj, entry=entry, compression=compression)
    return entry, [WriteReq(path=location, buffer_stager=stager)]


def prepare_read(
    entry: Entry,
    template: Any,
    callback: Callable[[Any], None],
) -> Tuple[List[ReadReq], List[Callable[[], None]]]:
    """Plan the restore of one leaf value into ``template``'s placement.

    Reference analog: io_preparer.py:377-401. Returns read requests plus
    finalizers to run after all reads complete (device assembly).
    """
    if isinstance(entry, PrimitiveEntry):
        callback(entry.get_value())
        return [], []
    if isinstance(entry, ObjectEntry):
        consumer = ObjectBufferConsumer(
            callback, checksum=entry.checksum, compression=entry.compression
        )
        return [ReadReq(path=entry.location, buffer_consumer=consumer)], []
    if isinstance(entry, (ArrayEntry, ShardedArrayEntry)):
        plan = ArrayRestorePlan(entry, template, callback)
        return plan.build_read_reqs(), [plan.finalize]
    raise TypeError(f"Cannot prepare read for entry type {type(entry)}")
