"""IO interfaces shared by the scheduler, preparers, and storage plugins.

TPU-native analog of reference torchsnapshot/io_types.py:15-71.

- ``BufferStager`` — produces the payload for one storage write; staging is
  where device→host (HBM→RAM) transfer and serialization happen, off the
  critical path inside a thread executor.
- ``BufferConsumer`` — absorbs the payload of one storage read; consuming
  is where deserialization and host→device placement happen.
- ``WriteReq``/``ReadReq`` pair a storage path with a stager/consumer.
- ``IOReq`` is the unit handed to a ``StoragePlugin``.
- ``StoragePlugin`` — async write/read/delete + sync close; concrete
  backends live in ``torchsnapshot_tpu.storage_plugins``.
"""

import abc
import asyncio
import io
import logging
import os
import random
import time
from concurrent.futures import Executor
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Union

from . import telemetry, tracing
from .telemetry import metrics as _metric_names

BufferType = Union[bytes, bytearray, memoryview]

logger = logging.getLogger(__name__)


# --------------------------------------------------------- storage-op hooks
#
# Observation seam for every storage-op boundary. Registered hooks receive
# ``(op, path)`` right before the op executes: plugin-level ops are emitted
# by wrappers (faultline's FaultPlugin emits "write"/"read"/"delete"/...),
# and backends with multi-step durability protocols emit their SUB-step
# boundaries too (fs.py emits "fs.write.tmp" → "fs.write.fsync" →
# "fs.write.rename" → "fs.write.dirsync"), so a fault-injection harness can
# place a crash BETWEEN the steps of a single logical write. The snapserve
# client announces every read-service RPC attempt as "snapserve.request"
# BEFORE touching the network, which is where kill_server/slow_server
# schedules hook in deterministically. A hook may
# raise — the exception propagates into the op exactly where a real failure
# (or process death) would strike. Zero cost when no hook is registered
# (one truthiness check per boundary).

_STORAGE_OP_HOOKS: List[Callable[[str, str], None]] = []


def add_storage_op_hook(hook: Callable[[str, str], None]) -> None:
    """Register ``hook(op, path)`` to observe every storage-op boundary."""
    _STORAGE_OP_HOOKS.append(hook)


def remove_storage_op_hook(hook: Callable[[str, str], None]) -> None:
    """Unregister a hook added by :func:`add_storage_op_hook`."""
    _STORAGE_OP_HOOKS.remove(hook)


def emit_storage_op(op: str, path: str) -> None:
    """Announce a storage-op boundary to registered hooks (may raise)."""
    if _STORAGE_OP_HOOKS:
        for hook in list(_STORAGE_OP_HOOKS):
            hook(op, path)


def _code_attr_http_status(exc: BaseException) -> Optional[int]:
    """The exception's ``.code`` as an int — but only when the exception
    plausibly comes from an HTTP client library. ``code`` is an
    overloaded attribute name (grpc status enums, library-specific error
    codes), so a bare integer match is not evidence of an HTTP status
    (ADVICE r3): misclassifying a retryable failure as a deterministic
    404/416 makes the retry layer give up and pollers misread errors.
    The gate: an ``errors``/``response`` attribute (google.api_core
    carries both) or an HTTP-flavored defining module."""
    code = getattr(exc, "code", None)
    if code is None:
        return None
    if not (
        hasattr(exc, "errors")
        or getattr(exc, "response", None) is not None
        or any(
            tok in type(exc).__module__
            for tok in ("google", "http", "urllib", "requests", "aiohttp")
        )
    ):
        return None
    try:
        return int(code)
    except (TypeError, ValueError):
        return None


def is_not_found_error(exc: BaseException) -> bool:
    """Whether a storage failure means "object does not exist".

    fs and memory plugins raise FileNotFoundError; cloud client not-found
    exception classes carry NotFound/NoSuchKey in their type name or a
    structured 404 status code. Not-found is deterministic: pollers treat
    it as "not yet", and the retry layer never retries it. Classification
    is structural (exception type + status-code attributes), never by
    message substring: a transient proxy error whose HTML body happens to
    contain "404"/"Not Found" (or a request id containing "404") must NOT
    be classified as a missing object — that would skip retries and make
    async-commit polling spin until timeout. Deliberately narrow — a
    stray KeyError from a plugin's internals is a bug to surface, not a
    missing object.
    """
    if isinstance(exc, FileNotFoundError):
        return True
    # Cloud-client exception classes: google.api_core.exceptions.NotFound,
    # botocore's NoSuchKey ClientError subclass, etc.
    for klass in type(exc).__mro__:
        if klass.__name__ in ("NotFound", "NoSuchKey", "NoSuchBucket"):
            return True
    # Structured status codes. google-api-core carries `.code` (int or
    # http.HTTPStatus); botocore ClientError carries
    # `.response["ResponseMetadata"]["HTTPStatusCode"]` and
    # `.response["Error"]["Code"]`.
    if _code_attr_http_status(exc) == 404:
        return True
    response = getattr(exc, "response", None)
    if isinstance(response, dict):
        error_code = response.get("Error", {}).get("Code")
        if error_code in ("404", "NoSuchKey", "NotFound", "NoSuchBucket"):
            return True
        status = response.get("ResponseMetadata", {}).get("HTTPStatusCode")
        if status == 404:
            return True
    return False


def is_range_not_satisfiable_error(exc: BaseException) -> bool:
    """Whether a storage failure means "requested byte range starts at or
    past the end of the object".

    GCS raises 416 RequestRangeNotSatisfiable and S3 raises InvalidRange
    (HTTP 416) when a ranged GET's start offset is >= the object length.
    ``verify()`` probes one byte past the expected end of large objects to
    detect trailing garbage — on these backends a *healthy* object answers
    that probe with 416, so the probe must classify it as "object ends
    exactly where the manifest implies", not as corruption. Like
    not-found, 416 is deterministic: the retry layer must not retry it.
    Classification is structural (exception type + status-code
    attributes), never by message substring — same rationale as
    :func:`is_not_found_error`.
    """
    for klass in type(exc).__mro__:
        if klass.__name__ in (
            "RequestRangeNotSatisfiable",  # google.api_core.exceptions
            "RequestedRangeNotSatisfiable",  # werkzeug/HTTP libs spelling
            "InvalidRange",
        ):
            return True
    response = getattr(exc, "response", None)
    if isinstance(response, dict):
        if response.get("Error", {}).get("Code") in ("416", "InvalidRange"):
            return True
        if response.get("ResponseMetadata", {}).get("HTTPStatusCode") == 416:
            return True
    return _code_attr_http_status(exc) == 416


# Storage-op retry policy (beyond reference parity: the reference has no
# retries anywhere — one transient object-store 5xx aborts the whole
# snapshot, SURVEY §5). Writes are whole-object puts, reads are (ranged)
# gets, deletes are idempotent — all safe to retry.
#
# Backoff is decorrelated-jitter (each delay drawn uniformly from
# [initial, prev*3], capped): pure exponential backoff keeps every rank
# of a pod on the SAME schedule, so after a shared-storage brownout all
# ranks re-hammer the recovering service in lockstep at exactly the
# moments it tries to come back. Jitter spreads the herd; the per-delay
# cap bounds any single wait; the elapsed budget bounds the whole retry
# episode so a permanently-failing op cannot pin a commit for
# attempts × cap seconds.
_STORAGE_RETRIES_ENV_VAR = "TPUSNAPSHOT_STORAGE_RETRIES"
_DEFAULT_STORAGE_ATTEMPTS = 3
_RETRY_BACKOFF_INITIAL_S = 0.25
_RETRY_DELAY_CAP_ENV_VAR = "TPUSNAPSHOT_STORAGE_RETRY_CAP_S"
_DEFAULT_RETRY_DELAY_CAP_S = 20.0
_RETRY_BUDGET_ENV_VAR = "TPUSNAPSHOT_STORAGE_RETRY_BUDGET_S"
_DEFAULT_RETRY_BUDGET_S = 600.0

# Deliberately unseeded: the whole point is that concurrent ranks draw
# DIFFERENT delays. Never feeds serialization or cross-rank decisions.
_retry_rng = random.Random()


def _storage_attempts() -> int:
    from .utils.env import env_int

    return 1 + max(
        0, env_int(_STORAGE_RETRIES_ENV_VAR, _DEFAULT_STORAGE_ATTEMPTS - 1)
    )


async def retry_storage_op(make_coro, desc: str):
    """Run ``await make_coro()`` with capped, decorrelated-jitter backoff
    on transient failures, under an overall elapsed budget
    (``TPUSNAPSHOT_STORAGE_RETRY_BUDGET_S``). ``make_coro`` is a zero-arg
    callable returning a fresh coroutine (a coroutine object cannot be
    awaited twice)."""
    from .utils.env import env_float

    attempts = _storage_attempts()
    cap = env_float(_RETRY_DELAY_CAP_ENV_VAR, _DEFAULT_RETRY_DELAY_CAP_S)
    if cap <= 0:
        cap = _DEFAULT_RETRY_DELAY_CAP_S
    # A cap below the initial backoff wins: the knob must keep meaning
    # "no single wait exceeds this" across its whole range, so the
    # jitter floor drops to the cap rather than the cap rising to the
    # floor (which would silently ignore sub-initial settings).
    floor = min(_RETRY_BACKOFF_INITIAL_S, cap)
    budget_s = env_float(_RETRY_BUDGET_ENV_VAR, _DEFAULT_RETRY_BUDGET_S)
    start = time.monotonic()
    prev_delay = floor
    for attempt in range(1, attempts + 1):
        attempt_start = time.monotonic()
        try:
            return await make_coro()
        except asyncio.CancelledError:
            raise
        except Exception as e:
            if (
                is_not_found_error(e)
                or is_range_not_satisfiable_error(e)
                or attempt == attempts
            ):
                raise
            # Decorrelated jitter: uniform over [floor, prev*3], capped.
            delay = min(
                cap,
                _retry_rng.uniform(floor, max(floor, prev_delay * 3.0)),
            )
            prev_delay = delay
            elapsed = time.monotonic() - start
            if elapsed + delay > budget_s:
                logger.warning(
                    f"Storage op {desc} failed (attempt {attempt}/"
                    f"{attempts}): {e!r}; retry budget exhausted "
                    f"({elapsed:.1f}s elapsed of {budget_s:g}s) — giving up"
                )
                raise
            # Always-on retry accounting next to the (tracing-gated)
            # instant, so instant-count == counter-count whenever a
            # trace is being recorded (tests/test_telemetry.py pins
            # this). The op *type* labels the counter — the full desc
            # carries a path, and paths are unbounded-cardinality.
            op_type = desc.split("(", 1)[0]
            telemetry.counter(
                _metric_names.STORAGE_RETRIES, op=op_type
            ).inc()
            telemetry.counter(
                _metric_names.STORAGE_RETRY_BACKOFF, op=op_type
            ).inc(delay)
            tracing.instant(
                "storage_retry",
                op=desc,
                attempt=attempt,
                attempt_s=round(time.monotonic() - attempt_start, 4),
                delay_s=round(delay, 4),
                error=type(e).__name__,
            )
            logger.warning(
                f"Storage op {desc} failed (attempt {attempt}/{attempts}): "
                f"{e!r}; retrying in {delay:.2f}s"
            )
            await asyncio.sleep(delay)


class BufferStager(abc.ABC):
    @abc.abstractmethod
    async def stage_buffer(self, executor: Optional[Executor] = None) -> BufferType:
        """Produce the payload bytes (device→host copy + serialize)."""

    @abc.abstractmethod
    def get_staging_cost_bytes(self) -> int:
        """Peak host memory charged against the budget while staging."""

    def release_staged(self) -> None:
        """The write pipeline is done with the staged payload:
        ``storage.write`` of it has returned or raised, or the pipeline
        unwinds before it got that far. A stager whose payload lives in
        a pooled buffer gives the lease back here (``staging_pool.py``).
        May be called again (the pipeline sweeps its requests on the way
        out): only the first call gives anything back. Nothing to give
        back by default."""


class BufferConsumer(abc.ABC):
    @abc.abstractmethod
    async def consume_buffer(
        self, buf: BufferType, executor: Optional[Executor] = None
    ) -> None:
        """Absorb the payload bytes (deserialize + host→device copy)."""

    @abc.abstractmethod
    def get_consuming_cost_bytes(self) -> int:
        """Peak host memory charged against the budget while consuming."""

    def get_deferred_cost_bytes(self) -> int:
        """The portion of :meth:`get_consuming_cost_bytes` whose backing
        allocation outlives this consumer's ``consume_buffer`` call (e.g.
        a split read's shared assembly buffer, freed only when the LAST
        sub-read lands). The scheduler refunds this portion through the
        releaser callback instead of at consume-task completion, so
        several concurrent split reads cannot overrun the budget by the
        sum of their object sizes. 0 for ordinary consumers."""
        return 0

    def set_cost_releaser(self, release: Callable[[int], None]) -> None:
        """Receive the scheduler's budget-release callback. Only called
        when :meth:`get_deferred_cost_bytes` returns non-zero; the
        consumer must invoke ``release(n)`` exactly once, when the
        deferred allocation is actually freed."""

    def reads_into_pool(self) -> bool:
        """Whether this read's payload may land in a buffer of the
        restores' staging pool (``staging_pool.py``): only where nothing
        keeps a view of the payload once the consumer is done with it
        (its bytes go to a device that copies them) and the consumer
        gives the buffer back itself. The buffer is of the read's range,
        or for a whole object of :meth:`get_consuming_cost_bytes`. False
        by default."""
        return False

    def hold_read_lease(self, lease: Any) -> None:
        """Own ``lease``, the pooled buffer this consumer's payload is
        read into (only where :meth:`reads_into_pool` said so): give it
        back once nothing reads the payload any more, on every path."""
        raise NotImplementedError

    def get_device_cost_bytes(self) -> int:
        """Device (HBM) bytes this consume deposits that outlive the
        consume call (streamed chunks awaiting assembly). The scheduler
        gates consume DISPATCH on a device-side budget so concurrent
        large restores cannot transiently exceed device memory. 0 for
        consumers that stay on host."""
        return 0

    def set_device_cost_releaser(
        self, release: Callable[[int], None]
    ) -> None:
        """Receive the device-budget release callback. Only called when
        :meth:`get_device_cost_bytes` returns non-zero; the consumer (or
        the assembly step it feeds) must invoke ``release(n)`` once the
        deposited device bytes are freed."""


@dataclass
class WriteReq:
    path: str
    buffer_stager: BufferStager


@dataclass
class ReadReq:
    path: str
    buffer_consumer: BufferConsumer
    # Byte range within the stored object ([start, end)); None = whole
    # object. Enables partial reads of large chunks during resharding.
    byte_range: Optional[tuple] = None


@dataclass
class IOReq:
    path: str
    buf: io.BytesIO = field(default_factory=io.BytesIO)
    byte_range: Optional[tuple] = None
    # Zero-copy payload. Writes: when set, plugins write `data` directly
    # instead of draining `buf`. Reads: plugins that can, return the
    # payload here instead of memcpy-ing it into `buf`.
    data: Optional[BufferType] = None
    # Reads: a plug-in that can fill a buffer may call this for a
    # writable one of the range's size (of a whole object, of the size
    # its entry gives), read the payload into it and set `data` to a
    # view of what it read; one that cannot ignores it.
    into: Optional[Callable[[], memoryview]] = None


def io_payload(io_req: "IOReq") -> BufferType:
    """The payload of a completed IOReq, whichever field carries it."""
    if io_req.data is not None:
        return io_req.data
    return io_req.buf.getbuffer()


class StoragePlugin(abc.ABC):
    # How many concurrent IO ops this backend profits from, read by the
    # scheduler as its per-pipeline concurrency caps. Object stores
    # (GCS/S3) want many parallel streams both ways; the fs plugin writes
    # one object at a time and reads through four streams (both measured,
    # see its comments).
    max_write_concurrency: int = 16
    max_read_concurrency: int = 16

    @abc.abstractmethod
    async def write(self, io_req: IOReq) -> None:
        ...

    @abc.abstractmethod
    async def read(self, io_req: IOReq) -> None:
        ...

    @abc.abstractmethod
    async def delete(self, path: str) -> None:
        ...

    async def list_prefix(self, prefix: str):
        """List stored object paths under ``prefix`` (same relative
        namespace as write/read/delete), or None when this backend cannot
        enumerate objects — sweep-style GC then degrades to
        referenced-objects-only deletion."""
        return None

    async def object_age_s(self, path: str) -> Optional[float]:
        """Seconds since ``path`` was last written, or None when the
        backend cannot tell. Sweep-style GC uses this to spare objects a
        concurrent in-progress take wrote moments ago; None means the
        object is swept unconditionally (pre-age-guard behavior)."""
        return None

    async def object_size_bytes(self, path: str) -> Optional[int]:
        """Stored size of ``path`` in bytes (a stat/HEAD, not a read), or
        None when the backend cannot tell. ``copy_to`` admits object
        entries — whose size the manifest does not record — against its
        host-memory budget with this; unknown sizes degrade to
        copy-alone admission."""
        return None

    def ensure_durable(self) -> None:
        """Make everything written through this plugin so far
        crash-durable. The commit protocol calls this on EVERY rank
        before the collective that leads to metadata publication, so a
        backend may defer per-object durability work (e.g. directory
        fsyncs) and settle it here in one batch. Default no-op: object
        stores are durable on write-ack."""

    @abc.abstractmethod
    def close(self) -> None:
        ...


class RetryingStoragePlugin(StoragePlugin):
    """Decorator adding transparent retries to every op of a plugin.

    Applied by ``url_to_storage_plugin`` so *all* storage traffic —
    payloads, the metadata commit, async-completion markers, random-access
    reads, deletes — shares one retry policy. A failed read attempt may
    have partially filled the request buffer, so reads reset it per
    attempt. Not-found propagates immediately (see
    :func:`is_not_found_error`).
    """

    def __init__(self, inner: StoragePlugin) -> None:
        self._inner = inner
        # Scheduler concurrency caps pass through to the real backend's.
        self.max_write_concurrency = inner.max_write_concurrency
        self.max_read_concurrency = inner.max_read_concurrency

    async def write(self, io_req: IOReq) -> None:
        await retry_storage_op(
            lambda: self._inner.write(io_req), f"write({io_req.path})"
        )

    async def read(self, io_req: IOReq) -> None:
        async def _attempt() -> None:
            io_req.buf.seek(0)
            io_req.buf.truncate()
            io_req.data = None
            await self._inner.read(io_req)

        await retry_storage_op(_attempt, f"read({io_req.path})")

    async def delete(self, path: str) -> None:
        await retry_storage_op(
            lambda: self._inner.delete(path), f"delete({path})"
        )

    async def list_prefix(self, prefix: str):
        return await retry_storage_op(
            lambda: self._inner.list_prefix(prefix), f"list({prefix})"
        )

    async def object_age_s(self, path: str) -> Optional[float]:
        # Retried like reads; a final failure propagates so the sweep
        # age guard can fail closed (spare the object) instead of
        # treating a throttled probe as "unknown age, sweep it".
        return await retry_storage_op(
            lambda: self._inner.object_age_s(path), f"age({path})"
        )

    async def object_size_bytes(self, path: str) -> Optional[int]:
        return await retry_storage_op(
            lambda: self._inner.object_size_bytes(path), f"size({path})"
        )

    def ensure_durable(self) -> None:
        self._inner.ensure_durable()

    def close(self) -> None:
        self._inner.close()
