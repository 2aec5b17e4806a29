"""Content fingerprints for incremental (deduplicated) snapshots.

Beyond reference parity: torchsnapshot rewrites every byte of every
tensor on every ``Snapshot.take`` — checkpointing a fine-tune whose
backbone is frozen pays the full device→host transfer and storage write
for data that has not changed since the previous snapshot. This module
provides a cheap, deterministic 128-bit content fingerprint that can be
computed **on device** (so an unchanged array is detected *before* any
device→host transfer) or on host for numpy-resident state.

Algorithm — ``xs128``: the logical payload (the uncompressed
little-endian C-order bytes that would be stored), zero-padded to a
multiple of 4 bytes, is viewed as a vector of uint32 words ``w_i``. For
four lanes ``k ∈ {0,1,2,3}``::

    F_k = sum_i  w_i * mix(i * GOLD + k * SALT + 1)   (mod 2^32)

where ``mix`` is the murmur3 finalizer (xor-shift / multiply
avalanche). Each lane is a random-weighted linear checksum: a change in
any word survives into ``F_k`` unless the weighted difference cancels
mod 2^32 — probability ~2^-32 per lane for non-adversarial changes,
~2^-128 over four independent lanes. Position-dependent weights make
the fingerprint sensitive to permutations as well as value changes
(a plain sum would not be).

Why linear instead of a cryptographic hash: the weighted sum is one
fused elementwise-multiply + reduce, which XLA compiles to a single
HBM-bandwidth pass on TPU with the ``iota``-derived weights fused in
(never materialized), and the identical arithmetic vectorizes in numpy
for host arrays. Collision resistance against an *adversary* is not a
goal — the fingerprint gates deduplication of a process's own training
state, the same trust model as rsync's rolling checksums.

Determinism contract: fingerprints are only ever compared
device-computed ↔ device-computed or host-computed ↔ host-computed for
the same leaf across successive takes (a leaf migrating between host
and device between takes may miss a dedup — never corrupt). The device
and host implementations follow the same spec and agree bit-for-bit on
the CPU backend (asserted in tests); agreement across platforms is not
load-bearing because a fingerprint MISMATCH always degrades to a full
write.
"""

from functools import partial
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

FINGERPRINT_ALGO = "xs128"

_GOLD = np.uint32(0x9E3779B1)  # 2^32 / golden ratio (Weyl increment)
_SALT = np.uint32(0x85EBCA77)  # per-lane offset
_N_LANES = 4

# murmur3 finalizer constants
_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)


def format_fingerprint(lanes: Any) -> str:
    """``"xs128:<32 hex>"`` from four uint32 lane values."""
    vals = np.asarray(lanes, dtype=np.uint64)
    return FINGERPRINT_ALGO + ":" + "".join(f"{int(v) & 0xFFFFFFFF:08x}" for v in vals)


# ----------------------------------------------------------------- device


def _mix_u32(h):
    """murmur3 finalizer on uint32 (jnp)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    h = h ^ (h >> 16)
    return h


def _device_words(x: jax.Array) -> jax.Array:
    """Reinterpret an array's data as a flat uint32 word vector.

    Sub-4-byte dtypes pack groups of ``4/itemsize`` elements into one
    word via a trailing-dimension bitcast; the tail is zero-padded. The
    exact word order within a group is whatever
    ``lax.bitcast_convert_type`` produces on this platform — stable for
    a given platform/jax version, which is all the determinism contract
    needs (see module docstring).
    """
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    itemsize = np.dtype(x.dtype).itemsize
    if itemsize not in (1, 2, 4, 8) or np.issubdtype(
        np.dtype(x.dtype), np.complexfloating
    ):
        # complex / exotic widths: no defined word view. Callers catch
        # and degrade to a full (un-deduplicated) write.
        raise ValueError(
            f"no device fingerprint for dtype {x.dtype} "
            f"(itemsize {itemsize})"
        )
    flat = x.reshape(-1)
    if itemsize == 4:
        return lax.bitcast_convert_type(flat, jnp.uint32)
    if itemsize == 8:
        return lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    # itemsize in (1, 2): pack ratio elements per uint32 word.
    ratio = 4 // itemsize
    narrow = lax.bitcast_convert_type(
        flat, jnp.uint8 if itemsize == 1 else jnp.uint16
    )
    pad = (-narrow.shape[0]) % ratio
    if pad:
        narrow = jnp.concatenate(
            [narrow, jnp.zeros((pad,), dtype=narrow.dtype)]
        )
    return lax.bitcast_convert_type(narrow.reshape(-1, ratio), jnp.uint32)


@partial(jax.jit, static_argnames=("slices",))
def _fingerprint_device_jit(
    x: jax.Array, slices: Optional[Tuple[Tuple[int, int], ...]] = None
) -> jax.Array:
    if slices is not None:
        x = x[tuple(slice(a, b) for a, b in slices)]
    w = _device_words(x)
    n = w.shape[0]
    # iota-derived weights fuse into the reduction — no O(n) weight
    # buffer is materialized.
    i = lax.iota(jnp.uint32, n)
    lanes = []
    for k in range(_N_LANES):
        salt = (int(_SALT) * k + 1) & 0xFFFFFFFF
        m = _mix_u32(i * jnp.uint32(_GOLD) + jnp.uint32(salt))
        lanes.append(jnp.sum(w * m, dtype=jnp.uint32))
    return jnp.stack(lanes)


def fingerprint_device_async(
    x: jax.Array, slices: Optional[Tuple[slice, ...]] = None
) -> jax.Array:
    """Dispatch the fingerprint computation on ``x``'s device; returns
    the (4,)-uint32 result array WITHOUT blocking. Call
    :func:`format_fingerprint` on it (or ``np.asarray`` it) to resolve.

    ``slices`` (static start/stop per dim) fingerprints a sub-box — the
    slice fuses into the jitted computation, so no chunk-sized buffer
    materializes for subdivided shards.
    """
    static = None
    if slices is not None:
        static = tuple(
            (
                0 if s.start is None else int(s.start),
                int(x.shape[d]) if s.stop is None else int(s.stop),
            )
            for d, s in enumerate(slices)
        )
    return _fingerprint_device_jit(x, static)


def resolve_fingerprints(results: list) -> list:
    """Resolve a batch of :func:`fingerprint_device_async` results with
    ONE device→host fetch per device: each individual 16-byte fetch
    pays a full link round trip, and an async take resolves one per
    leaf inside its stall. Returns a list aligned with ``results`` whose
    elements are fingerprint strings, or the per-item ``Exception`` on
    failure (mixed placements fall back to per-item fetches)."""
    import jax.numpy as jnp

    out: list = [None] * len(results)
    by_device: dict = {}
    for i, r in enumerate(results):
        try:
            dev = next(iter(r.devices()))
        # Placement probe on a possibly-failed result; grouping is an
        # optimization and the per-item path re-surfaces real errors.
        except Exception:  # snapcheck: disable=swallowed-exception -- placement probe
            dev = None
        by_device.setdefault(dev, []).append(i)
    for idxs in by_device.values():
        rows = None
        if len(idxs) > 1:
            try:
                rows = np.asarray(jnp.stack([results[i] for i in idxs]))
            # Per-item fallback below re-runs each fetch and KEEPS its
            # exception in the output, so nothing is lost here.
            except Exception:  # snapcheck: disable=swallowed-exception -- retried per-item
                rows = None  # mixed placements etc.: per-item fallback
        if rows is not None:
            for i, row in zip(idxs, rows):
                out[i] = format_fingerprint(row)
            continue
        for i in idxs:
            try:
                out[i] = format_fingerprint(np.asarray(results[i]))
            except Exception as e:
                out[i] = e
    return out


# --------------------------------------------------------- chunked variants
#
# Per-chunk fingerprints for the content-addressed chunk store
# (chunkstore.py): the logical payload is split into fixed-size byte
# chunks and each chunk is fingerprinted INDEPENDENTLY, with weights
# indexed from the chunk's own start — so a chunk's fingerprint equals
# :func:`fingerprint_host` of exactly that byte slice, and the same
# bytes appearing at the same chunk-grid position in a later take hash
# to the same content key. One jitted pass computes every chunk's four
# lanes (a (n_chunks, 4) device array): HBM-bandwidth bound, resolved
# with ONE device→host fetch per leaf.


@partial(jax.jit, static_argnames=("chunk_words",))
def _fingerprint_device_chunked_jit(
    x: jax.Array, chunk_words: int
) -> jax.Array:
    w = _device_words(x)
    pad = (-w.shape[0]) % chunk_words
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad,), dtype=jnp.uint32)])
    rows = w.reshape(-1, chunk_words)
    # Within-chunk indices: zero-padding a short tail chunk adds 0*m
    # terms, so the result equals fingerprint_host of the unpadded
    # slice (which pads to a word boundary the same way).
    i = lax.iota(jnp.uint32, chunk_words)
    lanes = []
    for k in range(_N_LANES):
        salt = (int(_SALT) * k + 1) & 0xFFFFFFFF
        m = _mix_u32(i * jnp.uint32(_GOLD) + jnp.uint32(salt))
        lanes.append(jnp.sum(rows * m[None, :], axis=1, dtype=jnp.uint32))
    return jnp.stack(lanes, axis=1)


def fingerprint_device_chunked_async(
    x: jax.Array, chunk_bytes: int
) -> jax.Array:
    """Dispatch per-chunk fingerprints over ``x``'s stored-byte layout,
    ``chunk_bytes`` per chunk (must be a positive multiple of 4);
    returns the (n_chunks, 4)-uint32 result WITHOUT blocking. Resolve
    with :func:`resolve_chunk_fingerprints` (or ``np.asarray``)."""
    if chunk_bytes <= 0 or chunk_bytes % 4:
        raise ValueError(
            f"chunk_bytes must be a positive multiple of 4; got "
            f"{chunk_bytes}"
        )
    return _fingerprint_device_chunked_jit(x, chunk_bytes // 4)


def resolve_chunk_fingerprints(results: list) -> list:
    """Resolve a batch of :func:`fingerprint_device_chunked_async`
    results; each output element is a list of fingerprint strings (one
    per chunk) or the per-item ``Exception``."""
    out: list = []
    for r in results:
        try:
            rows = np.asarray(r)
            out.append([format_fingerprint(row) for row in rows])
        except Exception as e:
            out.append(e)
    return out


def fingerprint_host_chunked(data: Any, chunk_bytes: int) -> list:
    """Per-chunk fingerprints of host bytes / a numpy array, matching
    :func:`fingerprint_host` over each ``chunk_bytes`` slice of the
    C-order little-endian payload.

    Bounded memory like :func:`fingerprint_host`: rows are processed in
    ≤ ``_HOST_CHUNK_WORDS``-word batches with plain uint32 wraparound
    arithmetic (one batch-sized product transient, never a
    payload-sized one), and only the tail chunk is pad-copied — a
    multi-GiB host-staged leaf must not double its RSS to be
    fingerprinted."""
    if chunk_bytes <= 0 or chunk_bytes % 4:
        raise ValueError(
            f"chunk_bytes must be a positive multiple of 4; got "
            f"{chunk_bytes}"
        )
    if isinstance(data, np.ndarray):
        if data.dtype == np.bool_:
            data = data.astype(np.uint8)
        buf = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
    else:
        buf = np.frombuffer(data, dtype=np.uint8)
    n = buf.shape[0]
    chunk_words = chunk_bytes // 4
    n_full = n // chunk_bytes
    n_chunks = -(-n // chunk_bytes) if n else 0
    i = np.arange(chunk_words, dtype=np.uint32)
    ms = []
    for k in range(_N_LANES):
        salt = np.uint32((int(_SALT) * k + 1) & 0xFFFFFFFF)
        ms.append(_mix_u32_np(i * _GOLD + salt))
    out = np.zeros((n_chunks, _N_LANES), dtype=np.uint32)
    body = buf[: n_full * chunk_bytes].view(np.uint32)
    batch_rows = max(1, _HOST_CHUNK_WORDS // chunk_words)
    for start in range(0, n_full, batch_rows):
        stop = min(n_full, start + batch_rows)
        rows = body[start * chunk_words : stop * chunk_words].reshape(
            stop - start, chunk_words
        )
        for k in range(_N_LANES):
            out[start:stop, k] = np.sum(
                rows * ms[k][None, :], axis=1, dtype=np.uint32
            )
    if n_chunks > n_full:
        tail = buf[n_full * chunk_bytes :]
        padded = np.zeros((chunk_bytes,), dtype=np.uint8)
        padded[: tail.shape[0]] = tail
        words = padded.view(np.uint32)
        for k in range(_N_LANES):
            out[n_full, k] = np.sum(words * ms[k], dtype=np.uint32)
    return [format_fingerprint(row) for row in out]


# ------------------------------------------------------------------- host

_HOST_CHUNK_WORDS = 1 << 22  # 16 MiB per pass


def _mix_u32_np(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * _M1
    h = h ^ (h >> np.uint32(13))
    h = h * _M2
    h = h ^ (h >> np.uint32(16))
    return h


def fingerprint_host(data: Any) -> str:
    """Fingerprint host bytes / a numpy array per the xs128 spec.

    Accepts ``bytes``/``memoryview``/``bytearray`` or an ``np.ndarray``
    (fingerprinted over its C-order little-endian bytes — the logical
    payload the snapshot would store).
    """
    if isinstance(data, np.ndarray):
        if data.dtype == np.bool_:
            data = data.astype(np.uint8)
        buf = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
    else:
        buf = np.frombuffer(data, dtype=np.uint8)
    n_pad = (-buf.shape[0]) % 4
    if n_pad:
        buf = np.concatenate([buf, np.zeros((n_pad,), dtype=np.uint8)])
    words = buf.view(np.uint32)
    lanes = np.zeros((_N_LANES,), dtype=np.uint32)
    # Chunked so a multi-GiB payload never materializes a same-sized
    # weight array on host.
    for start in range(0, words.shape[0], _HOST_CHUNK_WORDS):
        w = words[start : start + _HOST_CHUNK_WORDS]
        i = np.arange(start, start + w.shape[0], dtype=np.uint32)
        for k in range(_N_LANES):
            salt = np.uint32((int(_SALT) * k + 1) & 0xFFFFFFFF)
            m = _mix_u32_np(i * _GOLD + salt)
            # Array (not scalar) add: mod-2^32 wraparound is the spec,
            # and numpy warns about it on scalars only.
            lanes[k : k + 1] += np.sum(w * m, dtype=np.uint32)
    return format_fingerprint(lanes)
