"""Content-addressed cross-take chunk store (the dedup write plane).

Where the take path runs at the device→host link's rate, the only way
to make takes faster is to move FEWER bytes. ``incremental.py`` already
skips whole leaves whose
content fingerprint matches a ``base=`` snapshot; this module promotes
that to sub-leaf granularity with no ``base=`` argument at all:

- Each array payload is split into fixed-size chunks
  (``TPUSNAPSHOT_CHUNK_BYTES``, default 4 MiB) and every chunk is
  fingerprinted ON DEVICE in one batched jitted pass (fingerprint.py's
  ``xs128`` per chunk — HBM-bandwidth, before any device→host byte
  moves).
- A chunk is persisted only when the run's shared store
  (``<run-root>/.chunkstore/objects/<hh>/<key>``) does not already hold
  its bytes: the content key is ``<fingerprint>-<nbytes>-<codec>``, so
  consecutive takes share unchanged chunks even when a leaf is only
  *partially* dirty (trained embedding rows, LoRA-adjacent layers) and
  take cost becomes proportional to changed bytes at chunk granularity.
- A pluggable codec (codecs.py: zlib / zstd / opt-in lossy int8) runs
  between serialization and storage; the codec is recorded per chunk in
  the manifest and the decode fuses into the read→consume pipeline.

Manifest shape: the entry keeps its natural ``location`` (never
written), gains ``chunks`` records, and its ``base`` index names the
store root in ``SnapshotMetadata.base_paths`` (``"rel:.chunkstore"`` —
the store is a sibling of every step, so a moved snapshot family keeps
resolving).

GC model — derived refcounts, never mutable counters:

- Before a take reads the store index it drops a tiny per-rank INTENT
  marker (``intents/…``); delete/reconcile skip chunk freeing while a
  fresh intent exists, so a concurrent take's "this key is present"
  observation can never be invalidated mid-take. Intents are removed
  post-commit and age out if the take crashed.
- Before the metadata commit, rank 0 writes a REF document
  (``refs/<sha1(snapshot)>``) listing every chunk key the merged
  manifest references. A committed manifest therefore ALWAYS has a live
  ref doc — the invariant ``Snapshot.delete``/``reconcile`` free
  against. A ref doc whose snapshot never committed ages into debris.
- ``Snapshot.delete``: remove own ref doc (the refcount decrement),
  then free chunks no other live ref (committed, or younger than
  ``TPUSNAPSHOT_SWEEP_MIN_AGE_S``) lists. A crash at ANY op boundary
  leaks at most — chunks referenced by a committed manifest are
  structurally unreachable by the free (their ref doc survives).
- ``CheckpointManager.reconcile`` sweeps the debris: stale intents,
  stale refs, and unreferenced chunk objects (age-guarded like every
  sweep). faultline's crash matrix drives both paths at every op
  boundary (tests/test_chunkstore_gc.py; docs/FAULTS.md).
"""

import asyncio
import hashlib
import json
import logging
import os
import re
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from . import codecs, telemetry, tracing
from .io_preparer import ArrayBufferStager
from .io_types import (
    IOReq,
    StoragePlugin,
    WriteReq,
    io_payload,
    is_not_found_error,
)
from .manifest import ArrayEntry, Manifest, ShardedArrayEntry, SnapshotMetadata
from .serialization import compute_checksum
from .storage_plugin import (
    _parent_url,
    encode_base_ref,
    resolve_base_ref,
    url_to_storage_plugin,
)
from .telemetry import metrics as _metric_names
from .utils.env import env_float, env_int

logger = logging.getLogger(__name__)

STORE_DIRNAME = ".chunkstore"
OBJECTS_PREFIX = "objects/"
REFS_PREFIX = "refs/"
INTENTS_PREFIX = "intents/"

CHUNKS_ENV_VAR = "TPUSNAPSHOT_CHUNKS"
CHUNK_BYTES_ENV_VAR = "TPUSNAPSHOT_CHUNK_BYTES"
CHUNK_MIN_BYTES_ENV_VAR = "TPUSNAPSHOT_CHUNK_MIN_BYTES"
_DEFAULT_CHUNK_BYTES = 4 << 20
# Leaves smaller than this stay on the plain write path: a 2 KiB scalar
# buys no dedup worth a store round-trip + manifest record.
_DEFAULT_CHUNK_MIN_BYTES = 1 << 16

# Content-addressed object path: "objects/<hh>/xs128:<32hex>-<n>-<codec>"
_KEY_RE = re.compile(
    r"(?:^|/)objects/[0-9a-f]{2}/(xs128:[0-9a-f]{32}-\d+-[a-z0-9]+)$"
)

# Path marker routed to the store plugin by StoreRouterPlugin during the
# take's write pipeline. Never reaches the manifest.
ROUTE_PREFIX = "@chunkstore/"


def chunks_enabled_default() -> bool:
    return env_int(CHUNKS_ENV_VAR, 0) != 0


def chunk_bytes() -> int:
    raw = env_int(CHUNK_BYTES_ENV_VAR, _DEFAULT_CHUNK_BYTES)
    # Word-aligned so per-chunk fingerprints equal whole-payload slices.
    return max(4, raw - (raw % 4))


def chunk_min_bytes() -> int:
    return env_int(CHUNK_MIN_BYTES_ENV_VAR, _DEFAULT_CHUNK_MIN_BYTES)


def store_url_for(snapshot_path: str) -> Optional[str]:
    """The run-shared store root for a snapshot: a ``.chunkstore``
    sibling (CheckpointManager's ``step-<N>`` layout puts it at the
    manager base). None when the snapshot path has no parent — chunking
    is then disabled (there is no run to share chunks across)."""
    parent = _parent_url(snapshot_path.rstrip("/"))
    if parent is None:
        return None
    return f"{parent}/{STORE_DIRNAME}"


def chunk_key(fingerprint: str, nbytes: int, codec: Optional[str]) -> str:
    """Content key: fingerprint + logical length + codec. The length is
    cheap insurance on top of the 128-bit fingerprint; the codec keeps
    an int8-quantized store object from ever being referenced by a leaf
    that did not opt into lossy storage."""
    return f"{fingerprint}-{nbytes}-{codec or 'raw'}"


def chunk_object_path(key: str) -> str:
    hexpart = key.split(":", 1)[1]
    return f"{OBJECTS_PREFIX}{hexpart[:2]}/{key}"


def content_address_of(path: str) -> Optional[str]:
    """The content key embedded in a chunk-object storage path, or None
    for ordinary paths. Used by snapserve to key its content cache by
    chunk hash: a re-take of a mostly-unchanged model references the
    same keys, so the fleet's cache stays warm across manifests."""
    m = _KEY_RE.search(path)
    return m.group(1) if m else None


def ref_doc_name(snapshot_path: str) -> str:
    canon = snapshot_path.rstrip("/")
    return hashlib.sha1(canon.encode()).hexdigest()[:16]


def _min_age_s() -> float:
    return env_float("TPUSNAPSHOT_SWEEP_MIN_AGE_S", 3600.0)


# ------------------------------------------------------------------- stats


@dataclass
class ChunkStats:
    """Per-rank accounting for one take's chunk pass. ``note_stored``
    is called from staging threads (codec output sizes are only known
    there), so mutation is lock-guarded."""

    chunk_hits: int = 0
    chunk_misses: int = 0
    hit_bytes: int = 0  # logical bytes skipped via dedup
    logical_bytes: int = 0  # logical bytes of every chunked leaf
    written_logical_bytes: int = 0  # logical bytes of missed chunks
    stored_bytes: int = 0  # post-codec bytes actually written
    leaf_clean_bytes: int = 0  # bytes of leaves whose chunks ALL hit
    chunked_leaves: int = 0
    codec_in_bytes: int = 0  # logical bytes through a non-identity codec
    codec_out_bytes: int = 0
    codec_counts: Dict[str, int] = field(default_factory=dict)
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def note_stored(
        self, logical: int, stored: int, codec: Optional[str]
    ) -> None:
        with self._lock:
            self.stored_bytes += stored
            if codec is not None:
                self.codec_in_bytes += logical
                self.codec_out_bytes += stored
        telemetry.counter(
            _metric_names.CHUNKSTORE_BYTES, result="stored"
        ).inc(stored)
        if codec is not None:
            telemetry.counter(
                _metric_names.CODEC_BYTES, dir="in", codec=codec
            ).inc(logical)
            telemetry.counter(
                _metric_names.CODEC_BYTES, dir="out", codec=codec
            ).inc(stored)

    def fold_into_churn(self, note: Dict[str, Any]) -> None:
        """Merge this pass's accounting into the rank's churn note (the
        flight-recorder block the ledger sums across ranks)."""
        with self._lock:
            note.update(
                chunk_hits=self.chunk_hits,
                chunk_misses=self.chunk_misses,
                chunk_hit_bytes=self.hit_bytes,
                chunk_logical_bytes=self.logical_bytes,
                chunk_written_logical_bytes=self.written_logical_bytes,
                chunk_stored_bytes=self.stored_bytes,
                leaf_clean_bytes=self.leaf_clean_bytes,
                codec_in_bytes=self.codec_in_bytes,
                codec_out_bytes=self.codec_out_bytes,
            )


# ---------------------------------------------------------------- routing


class StoreRouterPlugin(StoragePlugin):
    """Routes ``@chunkstore/…`` paths to the store root during a take's
    write pipeline; everything else passes through to the snapshot's
    own plugin. Write-side only (the read side routes through the
    ordinary ``@base<N>/`` RefRouterPlugin via ``base_paths``). Close
    is the CALLER's job for both wrapped plugins — the router owns
    neither."""

    def __init__(self, inner: StoragePlugin, store: StoragePlugin) -> None:
        self._inner = inner
        self._store = store
        self.max_write_concurrency = inner.max_write_concurrency
        self.max_read_concurrency = inner.max_read_concurrency

    def _route(self, path: str) -> Tuple[StoragePlugin, str]:
        if path.startswith(ROUTE_PREFIX):
            return self._store, path[len(ROUTE_PREFIX):]
        return self._inner, path

    async def write(self, io_req: IOReq) -> None:
        plugin, path = self._route(io_req.path)
        if plugin is self._inner:
            await plugin.write(io_req)
            return
        routed = IOReq(path=path, data=io_req.data, buf=io_req.buf)
        await plugin.write(routed)

    async def read(self, io_req: IOReq) -> None:
        plugin, path = self._route(io_req.path)
        if plugin is self._inner:
            await plugin.read(io_req)
            return
        routed = IOReq(path=path, buf=io_req.buf, byte_range=io_req.byte_range)
        await plugin.read(routed)
        io_req.data = routed.data

    async def delete(self, path: str) -> None:
        plugin, p = self._route(path)
        await plugin.delete(p)

    async def list_prefix(self, prefix: str):
        plugin, p = self._route(prefix)
        return await plugin.list_prefix(p)

    async def object_age_s(self, path: str) -> Optional[float]:
        plugin, p = self._route(path)
        return await plugin.object_age_s(p)

    async def object_size_bytes(self, path: str) -> Optional[int]:
        plugin, p = self._route(path)
        return await plugin.object_size_bytes(p)

    def ensure_durable(self) -> None:
        self._store.ensure_durable()
        self._inner.ensure_durable()

    def close(self) -> None:
        # Owned by the take context (see _ChunkContext.cleanup); a
        # router close must not tear down plugins it merely borrows.
        pass


# ----------------------------------------------------------------- stagers


class ChunkStager(ArrayBufferStager):
    """Stages ONE missing content chunk: device-slices the element
    range (only the chunk's bytes cross device→host), encodes through
    the chunk's codec, back-patches the stored size + checksum into the
    manifest record, and hands the encoded bytes to the write pipeline.

    Subclasses :class:`io_preparer.ArrayBufferStager` so
    ``device_clone_write_reqs`` recognizes it: async takes clone the
    source array ONCE and every chunk stager of the leaf stages from
    the shared clone (the ``_data``/``_chunk_slices``/``_owns_data``
    seam). ``__init__``/``_stage_sync`` are fully overridden — the
    parent's prepare-time whole-array copy kickoff must never run for a
    chunk-granular stager."""

    def __init__(
        self,
        data: Any,
        elem_range: Tuple[int, int],
        record: Dict[str, Any],
        codec: Optional[str],
        dtype_name: str,
        nbytes: int,
        stats: ChunkStats,
        entry: Optional[ArrayEntry] = None,
    ) -> None:
        self._data = data
        self._chunk_slices = None  # clone/fingerprint seam compatibility
        self._owns_data = False
        self._elem_range = elem_range
        self._record = record
        self._codec = codec
        self._dtype_name = dtype_name
        self._nbytes = nbytes
        self._stats = stats
        self._entry = entry
        self.encode_stats: Optional[Tuple[float, int]] = None

    def kickoff_host_copy(self) -> None:
        # A whole-array prefetch would transfer the full leaf once per
        # chunk stager; the sliced stage below moves only this chunk.
        pass

    @property
    def payload_nbytes(self) -> int:
        return self._nbytes

    def get_staging_cost_bytes(self) -> int:
        return self._nbytes

    async def stage_buffer(self, executor=None):
        if executor is None:
            # Inline-staging escape hatch: every pipeline path passes an
            # executor; a caller opting out owns the stall trade-off.
            return self._stage_sync()  # snapcheck: disable=event-loop-blocking -- executor=None is the caller-owned inline path; all pipeline call sites pass an executor
        loop = asyncio.get_running_loop()
        # The executor thread's fresh context would attribute the encode
        # span to no trace — carry the take's trace id across the hop.
        tid = tracing.current_trace_id()

        def _stage_adopted():
            with tracing.adopt_trace(tid):
                return self._stage_sync()

        return await loop.run_in_executor(executor, _stage_adopted)

    def _stage_sync(self):
        import jax

        data = self._data
        a, b = self._elem_range
        if isinstance(data, jax.Array) and not isinstance(data, np.ndarray):
            # Device-side slice of the flat element range: only the
            # chunk's bytes cross the link.
            part = np.asarray(data.reshape(-1)[a:b])
            part = np.ascontiguousarray(part)
            payload = memoryview(part.reshape(-1).view(np.uint8))
        else:
            host = np.ascontiguousarray(np.asarray(data))
            flat = host.reshape(-1).view(np.uint8)
            itemsize = host.dtype.itemsize
            payload = memoryview(flat)[a * itemsize : b * itemsize]
            if not self._owns_data:
                payload = memoryview(bytes(payload))  # consistent cut
        self._data = None
        logical = len(payload)
        codec = self._codec
        t0 = time.monotonic()
        if codec is not None:
            try:
                with tracing.span(
                    "encode", codec=codec, bytes=logical
                ):
                    stored: Any = codecs.encode(
                        codec, payload, self._dtype_name
                    )
            except codecs.CodecUnsuitable as e:
                # Near-unreachable: lossy suitability is probed at plan
                # time (apply_chunkstore) and lossless codecs never
                # raise. Store identity bytes under the ORIGINAL key —
                # the write path is already fixed — and record c=None;
                # the read path's identity fallback self-heals a
                # mismatched hit (chunk read code, io_preparer.py).
                logger.warning(
                    f"codec {codec!r} unsuitable for chunk "
                    f"({e}); storing identity bytes"
                )
                codec = None
                stored = payload
            self.encode_stats = (time.monotonic() - t0, len(stored))
        else:
            stored = payload
        # Back-patch the record the manifest aliases (staging always
        # precedes the manifest consolidation, like checksums).
        rec = self._record
        rec["c"] = codec
        rec["sn"] = len(stored)
        rec["cs"] = compute_checksum(stored)
        self._stats.note_stored(logical, len(stored), codec)
        return stored

    @property
    def write_path(self) -> str:
        return ROUTE_PREFIX + chunk_object_path(self._record["k"])


# ------------------------------------------------------------- take context


@dataclass
class _ChunkContext:
    store_url: str
    store_plugin: StoragePlugin
    intent_path: Optional[str]
    stats: ChunkStats
    enabled: bool = True

    def wrap(self, storage: StoragePlugin) -> StoragePlugin:
        return StoreRouterPlugin(storage, self.store_plugin)

    def cleanup(self) -> None:
        """Post-commit (or post-failure): drop this rank's intent and
        close the store plugin. Best-effort — a surviving intent ages
        out; an aged intent merely defers chunk GC."""
        try:
            if self.intent_path is not None:
                asyncio.run(self.store_plugin.delete(self.intent_path))
        except Exception as e:
            if not is_not_found_error(e):
                logger.warning(f"chunkstore intent cleanup failed: {e!r}")
        finally:
            self.intent_path = None
            try:
                self.store_plugin.close()
            except Exception:  # pragma: no cover - best-effort teardown
                logger.warning("chunkstore plugin close failed", exc_info=True)


def _manifest_logical_paths(manifest: Manifest) -> Dict[int, str]:
    """``{id(ArrayEntry): logical path}`` for codec-plan matching —
    sharded/chunked-dense shard entries map to their parent path."""
    out: Dict[int, str] = {}
    for path, entry in manifest.items():
        if isinstance(entry, ArrayEntry):
            out[id(entry)] = path
        elif isinstance(entry, ShardedArrayEntry):
            for shard in entry.shards:
                out[id(shard.array)] = path
    return out


# One-time per-dtype probe results: device- and host-computed chunk
# fingerprints must agree BIT-FOR-BIT for chunk keys to content-verify
# at restore (unlike leaf dedup, where a divergence is only a missed
# hit). _device_words' sub-word packing is platform-defined, so the
# agreement is verified empirically once per (process, dtype) and
# divergent dtypes degrade to host-side fingerprinting (correct, just
# pays the D2H transfer the device pass would have skipped).
_FP_AGREEMENT: Dict[str, bool] = {}


def _device_fp_matches_host(dtype: Any) -> bool:
    name = str(np.dtype(dtype))
    cached = _FP_AGREEMENT.get(name)
    if cached is not None:
        return cached
    try:
        import jax.numpy as jnp

        from .fingerprint import (
            fingerprint_device_chunked_async,
            fingerprint_host_chunked,
            resolve_chunk_fingerprints,
        )

        if np.dtype(dtype) == np.bool_:
            host = np.arange(96) % 3 == 0
        else:
            host = (np.arange(96) % 251).astype(np.dtype(dtype))
        probe_bytes = 64  # multiple of 4, smaller than the payload
        dev = resolve_chunk_fingerprints(
            [
                fingerprint_device_chunked_async(
                    jnp.asarray(host), probe_bytes
                )
            ]
        )[0]
        ok = not isinstance(dev, Exception) and dev == (
            fingerprint_host_chunked(host, probe_bytes)
        )
    # Probe failure = no proven agreement: degrade to host hashing.
    except Exception:  # snapcheck: disable=swallowed-exception -- agreement probe; degrades to host hashing
        ok = False
    _FP_AGREEMENT[name] = ok
    if not ok:
        logger.warning(
            f"device and host chunk fingerprints disagree for dtype "
            f"{name} on this platform; chunk keys for {name} leaves "
            f"will be computed on host (correct, but pays the "
            f"device->host transfer)"
        )
    return ok


def _chunk_grid(
    total_elems: int, itemsize: int, target_bytes: int
) -> Tuple[int, int]:
    """(elems_per_chunk, n_chunks) with chunk byte-length a multiple of
    4 so per-chunk fingerprints align with whole-payload slices."""
    align = 4 // int(np.gcd(itemsize, 4)) if itemsize < 4 else 1
    elems = int(max(align, (target_bytes // itemsize) // align * align))
    n = int(max(1, -(-total_elems // elems)))
    return elems, n


def apply_chunkstore(
    manifest: Manifest,
    write_reqs: List[Any],
    *,
    rank: int,
    own_path: str,
    base_paths: List[str],
    codec_spec: Any = None,
    stats: Optional[ChunkStats] = None,
) -> Optional[_ChunkContext]:
    """Rewrite array write requests into content-addressed chunk
    writes, skipping every chunk the run's store already holds.

    Mutates ``manifest`` entries (``chunks``/``base``) and replaces
    deduplicated/chunked requests in ``write_reqs``. Collective-free;
    the store ref appended to ``base_paths`` is a pure function of the
    snapshot path, so every rank derives the identical namespace.
    Returns the context the caller must ``cleanup()`` after the commit
    (or failure), or None when chunking cannot run here (no parent
    directory / non-enumerable backend) — the take proceeds unchunked.
    """
    stats = stats if stats is not None else ChunkStats()
    # Validate the codec spec BEFORE any store side-effect: a bad
    # codec= / TPUSNAPSHOT_CODEC must fail the take as a clean config
    # error — with no intent marker left behind to defer the run's
    # chunk GC for an age-guard window.
    plan = codecs.resolve_codec_plan(codec_spec)
    store_url = store_url_for(own_path)
    if store_url is None:
        logger.warning(
            f"chunk dedup disabled: snapshot path {own_path!r} has no "
            f"parent directory to host the shared {STORE_DIRNAME} store"
        )
        return None
    # The store ref joins base_paths BEFORE any fallible store IO, on
    # every rank: base_paths must be a pure function of rank-uniform
    # inputs (entry `base` indices resolve against rank 0's merged
    # namespace), so a rank whose store probe fails must still derive
    # the same list as its peers — it then simply writes unchunked, and
    # the unused ref entry is inert.
    store_ref = encode_base_ref(store_url, own_path)
    if store_ref in base_paths:
        store_idx = base_paths.index(store_ref)
    else:
        store_idx = len(base_paths)
        base_paths.append(store_ref)
    store_plugin = url_to_storage_plugin(store_url)
    intent_path = None
    try:
        # Intent BEFORE the index read: delete/reconcile must not free
        # a chunk between our "present" observation and our ref doc.
        intent_path = f"{INTENTS_PREFIX}{uuid.uuid4().hex[:16]}-r{rank}"
        intent = IOReq(path=intent_path)
        intent.buf.write(
            json.dumps({"pid": os.getpid(), "rank": rank}).encode()
        )
        asyncio.run(store_plugin.write(intent))
        known = asyncio.run(store_plugin.list_prefix(OBJECTS_PREFIX))
        if known is None:
            logger.warning(
                f"chunk dedup disabled: backend for {store_url!r} cannot "
                f"enumerate objects (GC would be impossible)"
            )
            asyncio.run(store_plugin.delete(intent_path))
            store_plugin.close()
            return None
    except Exception:
        # A broken store must not fail the checkpoint — degrade to the
        # plain (unchunked) write path.
        logger.warning(
            f"chunk dedup disabled: store {store_url!r} unusable",
            exc_info=True,
        )
        try:
            store_plugin.close()
        # Best-effort teardown of a plugin already proven broken.
        except Exception:  # pragma: no cover; snapcheck: disable=swallowed-exception -- teardown of failed plugin
            pass
        return None

    ctx = _ChunkContext(
        store_url=store_url,
        store_plugin=store_plugin,
        intent_path=intent_path,
        stats=stats,
    )
    try:
        _apply_chunkstore_body(
            manifest,
            write_reqs,
            rank=rank,
            store_idx=store_idx,
            index={p.rsplit("/", 1)[-1] for p in known},
            plan=plan,
            stats=stats,
        )
    except BaseException:
        # A failure between the intent write and the take's normal
        # cleanup point would strand the intent (deferring the run's
        # chunk GC) and leak the plugin — tear down here and let the
        # take fail cleanly.
        ctx.cleanup()
        raise
    return ctx


def _apply_chunkstore_body(
    manifest: Manifest,
    write_reqs: List[Any],
    *,
    rank: int,
    store_idx: int,
    index: Set[str],
    plan: "codecs.CodecPlan",
    stats: ChunkStats,
) -> None:
    from .fingerprint import (
        fingerprint_device_chunked_async,
        fingerprint_host_chunked,
        resolve_chunk_fingerprints,
    )

    paths_by_entry = _manifest_logical_paths(manifest)
    target = chunk_bytes()
    min_bytes = chunk_min_bytes()

    import jax

    # Pass 1: select eligible requests, dispatch device fingerprints
    # (pipelined — jax's async dispatch overlaps the per-leaf kernels).
    selected = []  # (wr, entry, data, logical_path, grid, fp handle/strs)
    for wr in write_reqs:
        stager = wr.buffer_stager
        if not isinstance(stager, ArrayBufferStager):
            continue
        entry = stager._entry
        data = stager._data
        if (
            entry is None
            or data is None
            or not isinstance(entry, ArrayEntry)
            or entry.serializer != "raw"
            or stager._chunk_slices is not None  # box-sliced: plain path
        ):
            continue
        nbytes = stager._nbytes
        if nbytes < min_bytes:
            continue
        itemsize = np.dtype(
            np.uint8 if data.dtype == np.bool_ else data.dtype
        ).itemsize
        elems, n_chunks = _chunk_grid(
            nbytes // itemsize, itemsize, target
        )
        cbytes = elems * itemsize
        try:
            if (
                isinstance(data, jax.Array)
                and not isinstance(data, np.ndarray)
                and _device_fp_matches_host(data.dtype)
            ):
                fp = fingerprint_device_chunked_async(data, cbytes)
            else:
                # Host arrays — or device dtypes whose packing diverges
                # from host byte order on this platform (content keys
                # must verify against fingerprint_host at restore).
                fp = fingerprint_host_chunked(np.asarray(data), cbytes)
        except Exception as e:
            logger.warning(
                f"chunk fingerprint unavailable for "
                f"{paths_by_entry.get(id(entry))!r} ({e!r}); leaf stays "
                f"on the plain write path"
            )
            continue
        selected.append(
            (wr, entry, data, paths_by_entry.get(id(entry), ""), itemsize,
             elems, n_chunks, cbytes, nbytes, fp)
        )

    device_handles = [
        s[9] for s in selected if not isinstance(s[9], list)
    ]
    resolved = resolve_chunk_fingerprints(device_handles)
    resolved_iter = iter(resolved)

    # Pass 2: rewrite entries + build chunk write requests.
    replaced: Dict[int, List[Any]] = {}  # id(wr) -> new reqs ([] = drop)
    scheduled: Set[str] = set()  # keys already being written this take
    for (wr, entry, data, lpath, itemsize, elems, n_chunks, cbytes,
         nbytes, fp) in selected:
        fps = fp if isinstance(fp, list) else next(resolved_iter)
        if isinstance(fps, Exception):
            logger.warning(
                f"chunk fingerprint failed for {lpath!r} ({fps!r}); "
                f"leaf stays on the plain write path"
            )
            continue
        codec = plan.codec_for(
            lpath, dtype_name=entry.dtype, prng_impl=entry.prng_impl
        )
        if codecs.is_lossy(codec):
            # Plan-time suitability probe: a non-finite payload cannot
            # quantize (the block range poisons every element), and the
            # chunk keys/write paths are fixed HERE — degrade the whole
            # leaf to identity now rather than re-keying mid-stage.
            try:
                if isinstance(data, jax.Array) and not isinstance(
                    data, np.ndarray
                ):
                    import jax.numpy as jnp

                    finite = bool(jnp.isfinite(data).all())
                else:
                    finite = bool(np.isfinite(np.asarray(data)).all())
            # Suitability probe only: failure degrades to lossless.
            except Exception:  # snapcheck: disable=swallowed-exception -- suitability probe
                finite = False
            if not finite:
                logger.warning(
                    f"codec {codec!r} matched {lpath!r} but the payload "
                    f"is not finite-valued; storing without quantization"
                )
                codec = None
        total_elems = nbytes // itemsize
        records: List[Dict[str, Any]] = []
        new_reqs: List[Any] = []
        leaf_hit_bytes = 0
        for i in range(n_chunks):
            a = i * elems
            b = min(total_elems, a + elems)
            logical = (b - a) * itemsize
            key = chunk_key(fps[i], logical, codec)
            rec: Dict[str, Any] = {
                "k": key,
                "n": logical,
                "c": codec,
                "sn": None,
                "cs": None,
            }
            records.append(rec)
            present = key in index or key in scheduled
            if present:
                stats.chunk_hits += 1
                stats.hit_bytes += logical
                leaf_hit_bytes += logical
                # Stored size/checksum of a hit chunk are unknown here
                # (and unneeded: the read path verifies per chunk via
                # the checksum the WRITING take recorded — for hits we
                # re-derive at read time from the object itself, so a
                # hit record carries key + sizes only).
                rec.pop("sn")
                rec.pop("cs")
                telemetry.counter(
                    _metric_names.CHUNKSTORE_CHUNKS, result="hit"
                ).inc()
                telemetry.counter(
                    _metric_names.CHUNKSTORE_BYTES, result="hit"
                ).inc(logical)
            else:
                stats.chunk_misses += 1
                stats.written_logical_bytes += logical
                scheduled.add(key)
                stager = ChunkStager(
                    data,
                    (a, b),
                    rec,
                    codec,
                    entry.dtype,
                    logical,
                    stats,
                    entry=entry,
                )
                new_reqs.append(
                    WriteReq(path=stager.write_path, buffer_stager=stager)
                )
                telemetry.counter(
                    _metric_names.CHUNKSTORE_CHUNKS, result="miss"
                ).inc()
        stats.logical_bytes += nbytes
        stats.chunked_leaves += 1
        if leaf_hit_bytes == nbytes:
            stats.leaf_clean_bytes += nbytes
        entry.chunks = records
        entry.base = store_idx
        entry.checksum = None
        entry.compression = None
        replaced[id(wr)] = new_reqs

    if replaced:
        out: List[Any] = []
        for wr in write_reqs:
            if id(wr) in replaced:
                out.extend(replaced[id(wr)])
            else:
                out.append(wr)
        write_reqs[:] = out
        logger.info(
            f"chunkstore: rank {rank} deduplicated {stats.chunk_hits} "
            f"chunk(s) (~{stats.hit_bytes / (1 << 20):.1f} MiB), "
            f"writing {stats.chunk_misses}"
        )


def decode_and_verify_chunk(
    rec: Dict[str, Any],
    dtype_name: str,
    stored: Any,
    profile: Any = None,
    out: Optional[memoryview] = None,
) -> Optional[bytes]:
    """Decode one stored content chunk and verify its integrity —
    shared by the restore pipeline, ``Snapshot.verify``, and
    ``copy_to`` materialization so they can never disagree.

    Checks, per chunk and independent of which take wrote it:
    stored-size and stored-crc where THIS manifest recorded them (the
    chunks its own take wrote); then for lossless codecs the decoded
    bytes must fingerprint back to the content key (stronger than a
    crc, and available even for referenced-only chunks), while lossy
    (int8) frames self-verify their body crc inside ``decode``. A
    codec-tagged chunk whose decode fails but whose stored length
    equals the logical length falls back to identity (see
    ChunkStager's unsuitable-payload degrade) — the fingerprint check
    still gates the bytes. ``profile`` (a
    ``telemetry.consume_profile.PhaseProfile``, or None) splits the
    chunk's decode vs verify cost for the restore micro-profiler.

    ``out`` (an exactly-``n``-byte writable memoryview, or None) is the
    streaming fast path's zero-copy hand-off: identity-stored chunks
    are verified against the content key and copied ONCE into ``out``
    (returning None); codec chunks still decode to a transient and are
    returned for the caller to splice. Without ``out`` the decoded
    bytes are always returned — the pre-fastlane contract that
    ``verify``/``copy_to`` keep using."""
    from .fingerprint import fingerprint_host
    from .serialization import verify_checksum
    from .telemetry import consume_profile as _cprof

    key = rec["k"]
    logical_n = int(rec["n"])
    codec = rec.get("c")
    stored_n = rec.get("sn")
    # Stored-size/crc records are PER-WRITER observations, not the
    # content authority: two ranks missing the same key concurrently
    # both write it, and heterogeneous codec backends can emit
    # different-but-equivalent encodings — last write wins, and the
    # loser's recorded sn/cs then legitimately mismatch. Note the
    # mismatch, but let CONTENT verification below (fingerprint for
    # lossless, the self-checking frame for lossy) decide; only a
    # content failure is corruption.
    stale_note = None
    if stored_n is not None and len(stored) != int(stored_n):
        stale_note = (
            f"stored {len(stored)} bytes vs recorded {stored_n}"
        )
    else:
        try:
            with _cprof.substep(profile, "verify", len(stored)):
                verify_checksum(stored, rec.get("cs"))
        except Exception as e:
            stale_note = str(e)
    if out is not None and (codec is None or codec == "identity"):
        # Zero-copy fast path: identity chunks verify against the
        # content key on the STORED view and land in the caller's
        # assembly buffer with exactly one memcpy — no per-chunk
        # transient (the pre-fastlane flow copied twice: identity
        # decode + splice).
        if len(stored) != logical_n:
            raise RuntimeError(
                f"content chunk {key}: decoded {len(stored)} bytes, "
                f"expected {logical_n}"
                + (
                    f" (recorded-bytes mismatch: {stale_note})"
                    if stale_note
                    else ""
                )
            )
        expected_fp = key.rsplit("-", 2)[0]
        with _cprof.substep(profile, "verify", logical_n):
            actual_fp = fingerprint_host(stored)
        if actual_fp != expected_fp:
            raise RuntimeError(
                f"content chunk {key}: stored bytes decode to content "
                f"fingerprinting as {actual_fp} — the store object is "
                f"corrupt or mis-addressed"
                + (
                    f" (recorded-bytes mismatch: {stale_note})"
                    if stale_note
                    else ""
                )
            )
        if stale_note:
            logger.warning(
                f"content chunk {key}: recorded stored-size/crc do not "
                f"match the object ({stale_note}) but content "
                f"verification passed — likely a concurrent same-key "
                f"writer with a different encoder; serving the "
                f"verified bytes"
            )
        with _cprof.substep(profile, "reassemble", logical_n):
            out[:logical_n] = stored
        return None
    try:
        with _cprof.substep(profile, "decode", len(stored)):
            logical = codecs.decode(codec, stored, dtype_name)
    except Exception:
        if codec is not None and len(stored) == logical_n:
            logger.warning(
                f"content chunk {key}: codec {codec!r} decode failed "
                f"but stored length matches logical; treating as "
                f"identity"
            )
            logical = bytes(stored)
            codec = None
        else:
            raise
    if len(logical) != logical_n:
        raise RuntimeError(
            f"content chunk {key}: decoded {len(logical)} bytes, "
            f"expected {logical_n}"
            + (f" (recorded-bytes mismatch: {stale_note})" if stale_note else "")
        )
    if not codecs.is_lossy(codec):
        expected_fp = key.rsplit("-", 2)[0]
        with _cprof.substep(profile, "verify", len(logical)):
            actual_fp = fingerprint_host(logical)
        if actual_fp != expected_fp:
            raise RuntimeError(
                f"content chunk {key}: stored bytes decode to content "
                f"fingerprinting as {actual_fp} — the store object is "
                f"corrupt or mis-addressed"
                + (
                    f" (recorded-bytes mismatch: {stale_note})"
                    if stale_note
                    else ""
                )
            )
    if stale_note:
        logger.warning(
            f"content chunk {key}: recorded stored-size/crc do not "
            f"match the object ({stale_note}) but content verification "
            f"passed — likely a concurrent same-key writer with a "
            f"different encoder; serving the verified bytes"
        )
    return logical


def entry_is_lossy(entry: Any) -> bool:
    """Whether any of an entry's chunk records used a lossy codec —
    restored content then legitimately differs from the recorded
    whole-leaf fingerprint (restore(verify_device=True) skips it)."""
    recs = getattr(entry, "chunks", None) or []
    return any(codecs.is_lossy(rec.get("c")) for rec in recs)


# --------------------------------------------------------------- ref plane


def chunk_keys_of(manifest: Manifest) -> Set[str]:
    keys: Set[str] = set()
    for entry in manifest.values():
        if isinstance(entry, ArrayEntry) and entry.chunks:
            keys.update(rec["k"] for rec in entry.chunks)
        elif isinstance(entry, ShardedArrayEntry):
            for shard in entry.shards:
                if shard.array.chunks:
                    keys.update(rec["k"] for rec in shard.array.chunks)
    return keys


def manifest_has_chunks(manifest: Manifest) -> bool:
    for entry in manifest.values():
        if isinstance(entry, ArrayEntry) and entry.chunks:
            return True
        if isinstance(entry, ShardedArrayEntry) and any(
            s.array.chunks for s in entry.shards
        ):
            return True
    return False


async def awrite_ref_for(
    snapshot_path: str, metadata: SnapshotMetadata
) -> None:
    """Durably record the merged manifest's chunk references BEFORE the
    metadata commit (rank 0). Correctness-bearing, not best-effort: a
    committed manifest without a ref doc would be freeable by GC. A
    no-op for manifests without chunk entries."""
    keys = chunk_keys_of(metadata.manifest)
    if not keys:
        return
    store_url = store_url_for(snapshot_path)
    if store_url is None:  # pragma: no cover - chunking requires a parent
        raise RuntimeError(
            f"manifest carries chunk entries but {snapshot_path!r} has "
            f"no parent directory for the store"
        )
    storage = url_to_storage_plugin(store_url)
    try:
        doc = IOReq(path=REFS_PREFIX + ref_doc_name(snapshot_path))
        doc.buf.write(
            json.dumps(
                {
                    "path": encode_base_ref(snapshot_path, store_url),
                    "take_id": metadata.take_id,
                    "chunks": sorted(keys),
                }
            ).encode()
        )
        await storage.write(doc)
    finally:
        storage.close()


async def _aread_ref_docs(
    storage: StoragePlugin,
) -> List[Tuple[str, Optional[Dict[str, Any]]]]:
    """[(marker_path, parsed doc or None-on-parse-failure)] — callers
    FAIL CLOSED on None (an unreadable ref might protect live chunks)."""
    out: List[Tuple[str, Optional[Dict[str, Any]]]] = []
    for p in await storage.list_prefix(REFS_PREFIX) or []:
        try:
            io_req = IOReq(path=p)
            await storage.read(io_req)
            doc = json.loads(bytes(io_payload(io_req)).decode())
            if not isinstance(doc.get("chunks"), list):
                raise ValueError("malformed ref doc")
            out.append((p, doc))
        except Exception as e:
            logger.warning(f"unreadable chunk-ref doc {p}: {e!r}")
            out.append((p, None))
    return out


async def _alive_ref_keys(
    storage: StoragePlugin,
    store_url: str,
    min_age_s: float,
    exclude: Optional[str] = None,
    stale_out: Optional[List[str]] = None,
) -> Optional[Set[str]]:
    """Union of chunk keys protected by live ref docs (committed
    snapshot, or a young doc that may belong to an in-flight take).
    ``exclude`` names one marker path to skip (the deleting snapshot's
    own). Returns None when ANY doc is unreadable — freeing would be
    unsafe. Stale docs (old + no committed referencing metadata) are
    appended to ``stale_out`` for the caller to sweep."""
    from .snapshot import _aread_metadata_at

    live: Set[str] = set()
    for marker_path, doc in await _aread_ref_docs(storage):
        if marker_path == exclude:
            continue
        if doc is None:
            return None
        try:
            snap_url = resolve_base_ref(doc["path"], store_url)
        except Exception as e:
            # A malformed ref doc might be protecting live chunks:
            # fail CLOSED (no freeing this pass) and say why.
            logger.warning(
                f"malformed chunk-ref doc {marker_path}: {e!r}; "
                f"freeing nothing this pass"
            )
            return None
        committed_keys: Set[str] = set()
        committed = False
        try:
            md = await _aread_metadata_at(snap_url)
            committed_keys = chunk_keys_of(md.manifest)
            committed = bool(committed_keys)
        except Exception as e:
            # Only a definitive NOT-FOUND means "not committed" (the
            # uncommitted/deleted-referencer signal the age guard then
            # arbitrates). Anything else — a transient storage error, a
            # parse failure — might be hiding a COMMITTED snapshot
            # whose chunks we'd free: fail CLOSED, same as an
            # unreadable ref doc.
            if not is_not_found_error(e):
                logger.warning(
                    f"chunk GC: cannot determine whether {snap_url!r} "
                    f"is committed ({e!r}); freeing nothing this pass"
                )
                return None
            committed = False
        if committed:
            # Protect the COMMITTED MANIFEST's keys, not (only) the ref
            # doc's: a re-take to the same path overwrites the ref doc
            # with its new key set BEFORE its metadata commit, and a
            # crash there must not leave the still-committed old
            # snapshot's chunks unprotected. The doc's keys stay
            # protected too — they may belong to that in-flight
            # re-take.
            live.update(committed_keys)
            live.update(doc["chunks"])
            continue
        if min_age_s > 0:
            try:
                age = await storage.object_age_s(marker_path)
            # Unknown age fails CLOSED (treated as live) just below.
            except Exception:  # snapcheck: disable=swallowed-exception -- fails closed
                age = None
            if age is None or age < min_age_s:
                live.update(doc["chunks"])
                continue
        if stale_out is not None:
            stale_out.append(marker_path)
    return live


async def _ayoung_intent_present(
    storage: StoragePlugin, min_age_s: float, stale_out: Optional[List[str]] = None
) -> bool:
    """Whether any intent marker could belong to an in-flight take.
    With the age guard disabled (0) nothing is "young" — tests and
    offline GC get deterministic freeing."""
    young = False
    for p in await storage.list_prefix(INTENTS_PREFIX) or []:
        if min_age_s <= 0:
            if stale_out is not None:
                stale_out.append(p)
            continue
        try:
            age = await storage.object_age_s(p)
        # Unknown age fails CLOSED: treat as an in-flight take.
        except Exception:  # snapcheck: disable=swallowed-exception -- fails closed
            age = None
        if age is None or age < min_age_s:
            young = True
        elif stale_out is not None:
            stale_out.append(p)
    return young


def gc_snapshot_chunks(
    snapshot_path: str, metadata: SnapshotMetadata
) -> Dict[str, int]:
    """``Snapshot.delete``'s chunk-GC arm (the refcount decrement +
    conditional free). The caller has already removed the snapshot's
    metadata (the uncommit), so this snapshot no longer counts as a
    live referencer. Crash-safe at every op boundary:

    1. delete OWN ref doc — before this, every chunk stays protected
       by it; after, our chunks are protected only where other live
       refs list them, which is exactly the refcount semantics.
    2. skip freeing entirely while a fresh intent exists (an in-flight
       take may be deduplicating against chunks we'd free).
    3. free ``own keys − live keys``; a crash partway leaks only —
       ``reconcile`` re-drives the sweep.
    """
    out = {"freed": 0, "kept": 0, "skipped": 0}
    own_keys = chunk_keys_of(metadata.manifest)
    if not own_keys:
        return out
    store_url = store_url_for(snapshot_path)
    if store_url is None:
        return out
    min_age_s = _min_age_s()
    storage = url_to_storage_plugin(store_url)

    async def _run() -> None:
        own_marker = REFS_PREFIX + ref_doc_name(snapshot_path)
        try:
            await storage.delete(own_marker)
        except Exception as e:
            if not is_not_found_error(e):
                raise
        if await _ayoung_intent_present(storage, min_age_s):
            logger.info(
                f"chunk GC for {snapshot_path}: deferring chunk freeing "
                f"(a take appears to be in flight); reconcile will "
                f"reclaim once it settles"
            )
            out["skipped"] = len(own_keys)
            return
        live = await _alive_ref_keys(
            storage, store_url, min_age_s, exclude=own_marker
        )
        if live is None:
            logger.warning(
                f"chunk GC for {snapshot_path}: unreadable ref doc(s); "
                f"freeing nothing (reconcile can retry once they are "
                f"readable or aged)"
            )
            out["skipped"] = len(own_keys)
            return
        doomed = sorted(own_keys - live)
        out["kept"] = len(own_keys) - len(doomed)
        if not doomed:
            return
        # Re-check intents IMMEDIATELY before freeing: a take that
        # dropped its intent after the first check may have just
        # observed these chunks as present. (The residual window —
        # an intent written between this probe and the deletes — is
        # what the intent-before-index-read ordering plus the age
        # guard on production configs bounds.)
        if await _ayoung_intent_present(storage, min_age_s):
            out["skipped"] = len(doomed)
            logger.info(
                f"chunk GC for {snapshot_path}: a take started "
                f"mid-GC; deferring the free (reconcile re-drives)"
            )
            return
        for key in doomed:
            try:
                await storage.delete(chunk_object_path(key))
            except Exception as e:
                if not is_not_found_error(e):
                    raise
            out["freed"] += 1
            telemetry.counter(
                _metric_names.CHUNKSTORE_GC, action="freed"
            ).inc()

    try:
        asyncio.run(_run())
    finally:
        storage.close()
    return out


def reconcile_store(base_url: str) -> Dict[str, int]:
    """Reconcile's chunk-store janitor: sweep stale intents, stale ref
    docs (uncommitted + aged), and unreferenced chunk objects (age-
    guarded like every sweep). Leak-free convergence: after crashed
    deletes/takes settle past the age guard, exactly the chunks that
    live committed manifests reference remain."""
    out = {"freed": 0, "kept": 0, "stale_refs": 0, "stale_intents": 0}
    store_url = f"{base_url.rstrip('/')}/{STORE_DIRNAME}"
    min_age_s = _min_age_s()
    storage = url_to_storage_plugin(store_url)

    async def _run() -> None:
        objs = await storage.list_prefix(OBJECTS_PREFIX)
        refs = await storage.list_prefix(REFS_PREFIX)
        intents = await storage.list_prefix(INTENTS_PREFIX)
        if not objs and not refs and not intents:
            return
        stale_intents: List[str] = []
        if await _ayoung_intent_present(
            storage, min_age_s, stale_out=stale_intents
        ):
            logger.info(
                f"chunkstore reconcile at {store_url}: take in flight; "
                f"deferring"
            )
            return
        for p in stale_intents:
            try:
                await storage.delete(p)
                out["stale_intents"] += 1
            except Exception as e:
                if not is_not_found_error(e):
                    logger.warning(f"intent sweep of {p} failed: {e!r}")
        stale_refs: List[str] = []
        live = await _alive_ref_keys(
            storage, store_url, min_age_s, stale_out=stale_refs
        )
        if live is None:
            logger.warning(
                f"chunkstore reconcile at {store_url}: unreadable ref "
                f"doc(s); freeing nothing this pass"
            )
            return
        for p in stale_refs:
            try:
                await storage.delete(p)
                out["stale_refs"] += 1
            except Exception as e:
                if not is_not_found_error(e):
                    logger.warning(f"ref sweep of {p} failed: {e!r}")
        doomed_objs = [
            o for o in objs or [] if o.rsplit("/", 1)[-1] not in live
        ]
        out["kept"] += len(objs or []) - len(doomed_objs)
        if doomed_objs and await _ayoung_intent_present(
            storage, min_age_s
        ):
            # Same pre-free re-check as delete-GC: a take that began
            # after the first probe may have observed these chunks.
            logger.info(
                f"chunkstore reconcile at {store_url}: a take started "
                f"mid-sweep; deferring the free"
            )
            return
        for obj in doomed_objs:
            if min_age_s > 0:
                try:
                    age = await storage.object_age_s(obj)
                except Exception as e:
                    logger.warning(
                        f"sparing chunk {obj} (age probe failed: {e!r})"
                    )
                    continue
                if age is None or age < min_age_s:
                    out["kept"] += 1
                    continue
            try:
                await storage.delete(obj)
                out["freed"] += 1
                telemetry.counter(
                    _metric_names.CHUNKSTORE_GC, action="swept"
                ).inc()
            except Exception as e:
                if not is_not_found_error(e):
                    logger.warning(f"chunk sweep of {obj} failed: {e!r}")

    try:
        asyncio.run(_run())
    finally:
        storage.close()
    if out["freed"] or out["stale_refs"] or out["stale_intents"]:
        logger.info(f"chunkstore reconcile at {store_url}: {out}")
    return out
