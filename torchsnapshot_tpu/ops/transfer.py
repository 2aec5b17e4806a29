"""Device↔host transfer ops: chunked parallel gather + consistent-cut clone.

The two device-side primitives behind snapshot performance:

- :func:`parallel_device_get` — gather a large device array to host by
  slicing it on device along its largest dimension and transferring the
  slices over concurrent streams, so one stream's per-transfer latency
  overlaps the others' bytes. The gain over a single stream on a
  directly attached chip: not measured. Reference analog: the
  CUDA-stream staging thread pool (torchsnapshot io_preparer.py:199-210),
  re-thought for XLA's transfer model.
- :func:`device_clone` — on-device copies of a batch of arrays (sharding
  preserved). An HBM→HBM copy runs at memory bandwidth, which is what
  makes device-staged async snapshots' "stall = one on-device copy"
  possible.

Env knobs: ``TPUSNAPSHOT_TRANSFER_CHUNK_BYTES`` (default 8 MiB),
``TPUSNAPSHOT_TRANSFER_CONCURRENCY`` (default 32),
``TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER`` (test hook: chunk on CPU too).
"""

import math
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Sequence

import numpy as np

import jax

from ..telemetry import consume_profile as _cprof

_DEFAULT_TRANSFER_CHUNK_BYTES = 8 * 1024 * 1024
_DEFAULT_TRANSFER_CONCURRENCY = 32

_transfer_pool: Optional[ThreadPoolExecutor] = None
_transfer_pool_lock = threading.Lock()


def transfer_chunk_bytes() -> int:
    return int(
        os.environ.get(
            "TPUSNAPSHOT_TRANSFER_CHUNK_BYTES", _DEFAULT_TRANSFER_CHUNK_BYTES
        )
    )


def _get_transfer_pool() -> ThreadPoolExecutor:
    global _transfer_pool
    with _transfer_pool_lock:
        if _transfer_pool is None:
            _transfer_pool = ThreadPoolExecutor(
                max_workers=int(
                    os.environ.get(
                        "TPUSNAPSHOT_TRANSFER_CONCURRENCY",
                        _DEFAULT_TRANSFER_CONCURRENCY,
                    )
                ),
                thread_name_prefix="tpusnapshot-d2h",
            )
        return _transfer_pool


def should_chunk_transfer(arr: Any) -> bool:
    """Whether ``arr`` is a device array large enough for chunked gather."""
    if not isinstance(arr, jax.Array):
        return False
    try:
        platform = next(iter(arr.devices())).platform
    # Placement probe (tracers hide .devices()); "don't chunk" is the
    # safe default and the plain path surfaces real failures.
    except Exception:  # pragma: no cover; snapcheck: disable=swallowed-exception -- placement probe
        return False
    if platform == "cpu" and not os.environ.get(
        "TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER"
    ):
        # Host-backed arrays gather via memcpy (often zero-copy); device
        # slicing would only add copies. Env override exists for tests.
        return False
    shape = tuple(arr.shape)
    if not shape or max(shape) <= 1:
        return False
    nbytes = np.dtype(arr.dtype).itemsize * math.prod(shape)
    return nbytes >= 2 * transfer_chunk_bytes()


def parallel_device_get(
    arr: jax.Array,
    profile: Optional[_cprof.PhaseProfile] = None,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Gather ``arr`` to host via parallel chunked transfers.

    ``out``, where given, is the C-contiguous host array of ``arr``'s
    shape and dtype that the chunks are assembled in and that is
    returned: a take hands in a buffer of its pool
    (``io_preparer.ArrayBufferStager``), whose pages an earlier save has
    touched. Without it the array is allocated here.

    ``profile`` (the take's phase profile, telemetry/consume_profile.py)
    gets one note a chunk for each of ``slice``, ``d2h`` and ``copy``,
    one ``alloc`` (here, or where ``out`` was leased) and one
    ``fetch_wait`` a leaf, and the fetches' own thread-seconds as
    wall."""
    shape = tuple(arr.shape)
    dtype = np.dtype(arr.dtype)
    nbytes = dtype.itemsize * math.prod(shape)
    axis = max(range(len(shape)), key=lambda d: shape[d])
    n_chunks = min(shape[axis], max(1, -(-nbytes // transfer_chunk_bytes())))
    if out is None:
        with _cprof.substep(profile, "alloc", nbytes):
            out = np.empty(shape, dtype=dtype)
    bounds = [round(i * shape[axis] / n_chunks) for i in range(n_chunks + 1)]
    row_nbytes = nbytes // shape[axis]

    def _fetch(lo: int, hi: int) -> None:
        chunk_nbytes = (hi - lo) * row_nbytes
        with _cprof.wall(profile):
            with _cprof.substep(profile, "slice", chunk_nbytes):
                piece = jax.lax.slice_in_dim(arr, lo, hi, axis=axis)
            sel = tuple(
                slice(lo, hi) if d == axis else slice(None)
                for d in range(len(shape))
            )
            with _cprof.substep(profile, "d2h", chunk_nbytes):
                landed = np.asarray(piece)
            with _cprof.substep(profile, "copy", chunk_nbytes):
                out[sel] = landed

    pool = _get_transfer_pool()
    with _cprof.substep(profile, "fetch_wait", nbytes):
        futures = [
            pool.submit(_fetch, bounds[i], bounds[i + 1])
            for i in range(n_chunks)
            if bounds[i] < bounds[i + 1]
        ]
        errors = [f.exception() for f in futures]
    for err in errors:
        if err is not None:
            raise err
    return out


_DEFAULT_H2D_CHUNK_BYTES = 16 * 1024 * 1024


def h2d_chunk_bytes() -> int:
    from ..utils.env import env_int

    return env_int("TPUSNAPSHOT_H2D_CHUNK_BYTES", _DEFAULT_H2D_CHUNK_BYTES)


def should_chunk_h2d(arr: Any, device: Any) -> bool:
    """Whether a host buffer is worth pushing through the chunked path."""
    return _chunks_h2d(arr.nbytes, device)


def _chunks_h2d(nbytes: int, device: Any) -> bool:
    if getattr(device, "platform", None) == "cpu" and not os.environ.get(
        "TPUSNAPSHOT_FORCE_CHUNKED_TRANSFER"
    ):
        return False
    return nbytes >= 2 * h2d_chunk_bytes()


def h2d_put_copies(nbytes: int, device: Any) -> bool:
    """Whether the overlap engine's put of a host buffer of ``nbytes``
    leaves the device array no view of it, so the buffer may be refilled
    once the put has landed: always across a link; on a host-backed
    (CPU) device only through the chunked path, whose concatenate makes
    a buffer of its own (a plain ``device_put`` there may alias the
    numpy buffer)."""
    return getattr(device, "platform", None) != "cpu" or _chunks_h2d(
        nbytes, device
    )


def chunked_device_put(arr: np.ndarray, device: Any) -> Any:
    """Push a large host buffer to one device as a batch of medium-size
    chunks and reassemble on device.

    The runtime pipelines the per-chunk transfers of a batched put where
    one large transfer serializes (the gain on a directly attached chip:
    not measured). The reassembly is a flat 1-D concatenate + reshape:
    both layout-preserving, so the device-side cost is one HBM copy.
    Transient HBM footprint is 2× the array (chunks + result), matching
    the take path's on-device clone.
    """
    import jax.numpy as jnp

    flat = np.ascontiguousarray(arr).reshape(-1)
    itemsize = flat.dtype.itemsize
    chunk_elems = max(1, h2d_chunk_bytes() // itemsize)
    pieces = [
        flat[i : i + chunk_elems] for i in range(0, flat.size, chunk_elems)
    ]
    parts = jax.device_put(pieces, [device] * len(pieces))
    return jnp.concatenate(parts).reshape(arr.shape)


# ------------------------------------------------------------- H2D probe
#
# One-shot hardware-bound measurement for the restore flight report
# (snapxray): consume GB/s only means something as a FRACTION of what
# the link could do. Memoized per process — the report wants an
# order-of-magnitude anchor, not a bracketing measurement.

_H2D_PROBE_BYTES_ENV_VAR = "TPUSNAPSHOT_H2D_PROBE_BYTES"
_DEFAULT_H2D_PROBE_BYTES = 32 * 1024 * 1024

_h2d_probe_lock = threading.Lock()
_h2d_probe_memo: List[Optional[float]] = []


def probe_h2d_gbps(refresh: bool = False) -> Optional[float]:
    """Measured host→device bandwidth (GB/s) via the same chunked-put
    transfer the restore path uses, synced by a forced device reduction
    (``device_put`` is asynchronous). Best of two
    runs, each with a FRESH host buffer — re-putting the same array
    measures a cached staging path, not a restore. Memoized; ``refresh``
    re-measures. Returns None when disabled
    (``TPUSNAPSHOT_H2D_PROBE_BYTES=0``) or the probe fails (no device)."""
    from ..utils.env import env_int

    with _h2d_probe_lock:
        if _h2d_probe_memo and not refresh:
            return _h2d_probe_memo[0]
    nbytes = env_int(_H2D_PROBE_BYTES_ENV_VAR, _DEFAULT_H2D_PROBE_BYTES)
    result: Optional[float] = None
    if nbytes > 0:
        try:
            import time

            import jax.numpy as jnp

            device = jax.devices()[0]
            force = jax.jit(jnp.sum)
            rng = np.random.default_rng(11)
            n = max(1, nbytes // 4)
            best = 0.0
            for _ in range(2):
                host = rng.standard_normal(n, dtype=np.float32)
                begin = time.monotonic()
                arr = chunked_device_put(host, device)
                float(force(arr))
                elapsed = time.monotonic() - begin
                if elapsed > 0:
                    best = max(best, host.nbytes / 1024**3 / elapsed)
                arr.delete()
                del host
            result = best if best > 0 else None
        # Capability probe: a backend without a usable device (or one
        # that rejects delete()) yields "no probe", never a failed
        # restore report.
        except Exception:  # snapcheck: disable=swallowed-exception -- capability probe
            result = None
    with _h2d_probe_lock:
        if _h2d_probe_memo:
            _h2d_probe_memo[0] = result
        else:
            _h2d_probe_memo.append(result)
    return result


# ------------------------------------------------------- H2D overlap engine
#
# The streaming-restore fast path's transfer stream: a depth-limited
# worker pool that owns ALL host→device placement the restore pipeline
# wants off its consume executors. Consumers submit a host buffer the
# moment its decode+verify completes and go back to consuming; the
# engine runs the (chunked) put, waits for the bytes to land
# (block_until_ready — device_put is asynchronous), accounts the wall
# into the restore's consume profile
# as ``h2d_overlap``, and fires the caller's done-callback. Depth 2
# (``TPUSNAPSHOT_H2D_DEPTH``) is classic double buffering: one chunk's
# bytes ride the link while the next chunk's decode/verify/submit
# proceeds — the H2D mirror of how take double-buffers D2H through the
# chunked transfer pool above.

_H2D_DEPTH_ENV_VAR = "TPUSNAPSHOT_H2D_DEPTH"
_DEFAULT_H2D_DEPTH = 2


def h2d_depth() -> int:
    from ..utils.env import env_int

    return max(1, env_int(_H2D_DEPTH_ENV_VAR, _DEFAULT_H2D_DEPTH))


class H2DPipeline:
    """Depth-limited asynchronous host→device transfer engine."""

    def __init__(self, depth: Optional[int] = None) -> None:
        self._pool = ThreadPoolExecutor(
            max_workers=depth if depth is not None else h2d_depth(),
            thread_name_prefix="tpusnapshot-h2d",
        )

    def submit(self, host: Any, device: Any, profile: Any = None):
        """Schedule ``host`` (a numpy buffer) onto ``device``; returns a
        ``concurrent.futures.Future`` resolving to the device array
        AFTER the bytes have crossed the link. Exceptions (including
        faultline's SimulatedCrash BaseException) resolve into the
        future — callers must surface them before publishing anything
        assembled from sibling transfers."""
        nbytes = int(getattr(host, "nbytes", len(host)))

        def _transfer() -> Any:
            from .. import telemetry
            from ..telemetry import metrics as _metric_names

            t0 = time.monotonic()
            # Union-time accounting (overlap_span): the profile's
            # h2d_overlap seconds advance once across concurrent
            # workers so bytes/seconds is delivered link throughput;
            # the process counter below keeps plain per-call walls.
            with _cprof.overlap_span(profile, nbytes):
                if should_chunk_h2d(host, device):
                    dev = chunked_device_put(host, device)
                else:
                    dev = jax.device_put(host, device)
                jax.block_until_ready(dev)
            elapsed = time.monotonic() - t0
            telemetry.counter(_metric_names.H2D_OVERLAP_SECONDS).inc(
                elapsed
            )
            telemetry.counter(_metric_names.H2D_OVERLAP_BYTES).inc(nbytes)
            return dev

        return self._pool.submit(_transfer)


_h2d_pipeline: Optional[H2DPipeline] = None
_h2d_pipeline_lock = threading.Lock()


def h2d_pipeline() -> H2DPipeline:
    global _h2d_pipeline
    with _h2d_pipeline_lock:
        if _h2d_pipeline is None:
            _h2d_pipeline = H2DPipeline()
        return _h2d_pipeline


def _reset_h2d_pipeline_for_tests() -> None:
    global _h2d_pipeline
    with _h2d_pipeline_lock:
        _h2d_pipeline = None


def is_oom_error(exc: BaseException) -> bool:
    if isinstance(exc, MemoryError):
        return True
    text = str(exc)
    return "RESOURCE_EXHAUSTED" in text or "Out of memory" in text


def device_clone(arrays: Sequence[jax.Array]) -> Optional[List[jax.Array]]:
    """On-device copies of ``arrays`` (shardings preserved). Returns
    None — with partial clones released — if the device ran out of
    memory and the synchronous OOM check is enabled.

    The batched ``block_until_ready`` exists ONLY for that OOM check:
    the fallback to host staging must be decided while the caller's
    original arrays are still valid (after ``async_take`` returns they
    may be donated away). It costs one host↔device round trip plus the
    HBM copies themselves. Deployments with known HBM headroom can set
    ``TPUSNAPSHOT_CLONE_OOM_CHECK=0`` to skip it: a (now unhandled)
    clone OOM then surfaces when the background drain first stages from
    the poisoned clone — failing the take at ``wait()`` instead of
    falling back to host staging. Consistency does not depend on the
    wait either way: the runtime orders the clone before any later
    computation and keeps source buffers alive for pending consumers.
    """
    import jax.numpy as jnp

    check_oom = os.environ.get("TPUSNAPSHOT_CLONE_OOM_CHECK", "1") != "0"
    clones: List[jax.Array] = []
    try:
        for arr in arrays:
            clones.append(jnp.copy(arr))
        # One batched wait, not a per-array loop: each blocking call pays
        # a full host↔device round trip.
        if check_oom:
            jax.block_until_ready(clones)
    except Exception as e:
        if is_oom_error(e):
            for clone in clones:
                try:
                    clone.delete()
                # Freeing partially-materialized clones during OOM
                # unwind; the OOM itself is what the caller reports.
                except Exception:  # pragma: no cover; snapcheck: disable=swallowed-exception -- OOM unwind
                    pass
            return None
        raise
    return clones
