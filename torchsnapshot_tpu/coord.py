"""Coordination shim: small object collectives over a KV store.

TPU-native analog of reference torchsnapshot/pg_wrapper.py:13-57. The
snapshot protocol needs only *tiny* object collectives — key lists, glob
matches, manifests (kilobytes) — plus barriers; bulk tensor data goes
process→storage, never process→process (SURVEY §5). So instead of a
NCCL/gloo process group, the backend is a key-value store:

- ``NoOpCoordinator`` — single-process; every collective degrades to the
  identity (reference pg_wrapper.py:26-29).
- ``StoreCoordinator`` — generic collectives over an abstract blocking KV
  store, with three stores:

  - ``DictStore`` — in-process shared dict (threaded multi-"rank" tests);
  - ``FileStore`` — a directory on a shared filesystem (multi-process
    tests, single-node launches);
  - ``JaxStore`` — the ``jax.distributed`` coordination service (DCN),
    the production path on multi-host TPU pods.

``get_coordinator()`` picks ``JaxStore`` automatically when
``jax.distributed`` is initialized, else ``NoOpCoordinator`` — mirroring
the reference's "degrade gracefully when dist is uninitialized" contract.

Large blobs (> ~1 MB) are chunked through the store transparently, since
coordination-service values have size limits (SURVEY §7 hard part #3).
"""

import abc
import base64
import logging
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from . import telemetry, tracing

logger = logging.getLogger(__name__)

_DEFAULT_TIMEOUT_S = 300.0
_CHUNK = 512 * 1024  # chunk size for large values through the KV store
# Max broadcast generations a source lets go unacked before it blocks on
# the oldest one's acks. A free-running source outpaces its receivers
# indefinitely (it never blocks), so purely lazy ack collection would
# never fire in a broadcast-only loop; the window bounds live keys at
# O(window x world) and doubles as backpressure.
_BC_WINDOW = 8


class Store(abc.ABC):
    """A blocking KV store: set once, get blocks until the key exists."""

    @abc.abstractmethod
    def set(self, key: str, value: bytes) -> None:
        ...

    @abc.abstractmethod
    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        ...

    def delete(self, key: str) -> None:
        """Best-effort removal of a key (used by collective-key GC).

        Deleting an absent key is a no-op. The default is a no-op for
        stores that cannot delete — GC then degrades to unbounded keys,
        which is what every store did before GC existed.
        """

    def try_get(self, key: str) -> Optional[bytes]:
        """Non-blocking best-effort read: the value if the key exists
        *now*, else ``None``. Used by lazy broadcast-ack collection; a
        false ``None`` (e.g. a slow round-trip on a remote store) only
        defers GC to a later proof of progress, never affects
        correctness. Default: poll :meth:`get` with a tiny timeout."""
        try:
            return self.get(key, timeout_s=0.05)
        # A short-poll miss IS the expected "absent now" answer, and this
        # probe runs per pending ack — logging would flood steady state.
        except Exception:  # snapcheck: disable=swallowed-exception -- absent-now probe
            return None


class DictStore(Store):
    """In-process store shared between threads simulating ranks."""

    def __init__(self) -> None:
        self._data: Dict[str, bytes] = {}
        self._cond = threading.Condition()

    def set(self, key: str, value: bytes) -> None:
        with self._cond:
            self._data[key] = value
            self._cond.notify_all()

    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while key not in self._data:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(f"Timed out waiting for key: {key}")
                self._cond.wait(timeout=remaining)
            return self._data[key]

    def delete(self, key: str) -> None:
        with self._cond:
            self._data.pop(key, None)

    def try_get(self, key: str) -> Optional[bytes]:
        with self._cond:
            return self._data.get(key)

    def key_count(self) -> int:
        with self._cond:
            return len(self._data)


class FileStore(Store):
    """Directory-backed store for multi-process coordination on one node
    (or any shared filesystem). Writes are atomic via rename."""

    def __init__(self, path: str) -> None:
        self.path = path
        os.makedirs(path, exist_ok=True)

    def _file(self, key: str) -> str:
        safe = key.replace("/", "__")
        return os.path.join(self.path, safe)

    def set(self, key: str, value: bytes) -> None:
        target = self._file(key)
        fd, tmp = tempfile.mkstemp(dir=self.path)
        with os.fdopen(fd, "wb") as f:
            f.write(value)
        # No fsync: coordination keys are ephemeral per-generation values.
        # close() above precedes the rename, so live readers — including
        # NFS close-to-open peers — always see full data, and a host
        # crash kills the whole generation; durability buys nothing and
        # would cost an fsync per 512KB chunk on the collective hot path.
        # snapcheck: disable=durability-order -- ephemeral coordination keys
        os.replace(tmp, target)

    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        target = self._file(key)
        deadline = time.monotonic() + timeout_s
        delay = 0.001
        while True:
            try:
                with open(target, "rb") as f:
                    return f.read()
            except FileNotFoundError:
                if time.monotonic() > deadline:
                    raise TimeoutError(f"Timed out waiting for key: {key}")
                time.sleep(delay)
                delay = min(delay * 2, 0.05)

    def delete(self, key: str) -> None:
        try:
            os.unlink(self._file(key))
        except OSError:
            # Best-effort (Store.delete contract): a stale-handle/perms
            # hiccup on a shared filesystem must never fail the snapshot
            # whose collective triggered the GC.
            pass

    def try_get(self, key: str) -> Optional[bytes]:
        try:
            with open(self._file(key), "rb") as f:
                return f.read()
        except OSError:
            return None

    def key_count(self) -> int:
        return len(os.listdir(self.path))


class JaxStore(Store):
    """The jax.distributed coordination-service KV store (DCN).

    Values are base64-encoded because the service stores strings —
    1.33x the raw bytes vs hex's 2x (r2), which matters for the
    chunked large-value path (every byte is DCN traffic through one
    service). KV values live only within one collective generation, and
    all ranks of a job must run the same library version (the standard
    contract for any collective library), so no cross-encoding
    compatibility is attempted.
    """

    def __init__(self) -> None:
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "jax.distributed is not initialized; call "
                "jax.distributed.initialize() first."
            )
        self._client = client

    def set(self, key: str, value: bytes) -> None:
        self._client.key_value_set(
            key, base64.b64encode(value).decode("ascii")
        )

    def get(self, key: str, timeout_s: float = _DEFAULT_TIMEOUT_S) -> bytes:
        try:
            val = self._client.blocking_key_value_get(
                key, int(timeout_s * 1000)
            )
        except Exception as e:
            # The coordination service surfaces expiry as a backend
            # RuntimeError (DEADLINE_EXCEEDED), not TimeoutError.
            # Normalize so the collectives' rank-naming timeout handling
            # works identically on every Store backend.
            # Match only the structured status token — a broader match
            # (any message mentioning "deadline") would rewrite
            # connection/retry errors into TimeoutError and make the
            # collectives blame a healthy peer rank.
            if "DEADLINE_EXCEEDED" in str(e):
                raise TimeoutError(
                    f"Timed out waiting for key: {key}"
                ) from e
            raise
        return base64.b64decode(val.encode("ascii"), validate=True)

    def delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(key)
        except Exception:
            # Best-effort: a delete that races a service restart must
            # never fail a snapshot — but the failure is still visible at
            # debug level so a GC that silently stops collecting is
            # diagnosable.
            logger.debug(
                f"coordination-service delete of {key} failed", exc_info=True
            )

    def try_get(self, key: str) -> Optional[bytes]:
        try:
            val = self._client.key_value_try_get(key)
        except Exception as e:
            # Non-blocking probe: absence and transient failure both mean
            # "not observable now"; GC just defers (see Store.try_get).
            # Absence (NOT_FOUND) is the steady-state answer for pending
            # broadcast acks — logging it would flood DEBUG output — so
            # only genuinely unexpected failures leave a trace.
            if "NOT_FOUND" not in str(e):
                logger.debug(
                    f"coordination-service try_get of {key} failed",
                    exc_info=True,
                )
            return None
        return base64.b64decode(val.encode("ascii"), validate=True)


def format_rank_list(ranks: List[int], noun: str = "rank") -> str:
    """``[17]`` → "rank 17"; ``[1,2,3,7]`` → "ranks 1-3, 7". Runs
    compress to ranges so a pod-scale stall (thousands of absent ranks)
    reads as a handful of spans, not a 10 KB comma list. ``noun``
    re-labels the members (the hot tier names "peer host 3" /
    "peer hosts 0-2" with the same compression). Input must be sorted
    ascending; empty input reads as "no <noun>s"."""
    if not ranks:
        return f"no {noun}s"
    if len(ranks) == 1:
        return f"{noun} {ranks[0]}"
    spans = []
    start = prev = ranks[0]
    for r in ranks[1:]:
        if r == prev + 1:
            prev = r
            continue
        spans.append(f"{start}-{prev}" if prev > start else str(start))
        start = prev = r
    spans.append(f"{start}-{prev}" if prev > start else str(start))
    return f"{noun}s " + ", ".join(spans)


class Coordinator(abc.ABC):
    """Collective interface used by Snapshot (reference PGWrapper)."""

    @abc.abstractmethod
    def get_rank(self) -> int:
        ...

    @abc.abstractmethod
    def get_world_size(self) -> int:
        ...

    @abc.abstractmethod
    def barrier(self, timeout_s: Optional[float] = None) -> None:
        """Block until every rank arrives.

        ``timeout_s`` overrides the coordinator's default wait for this
        one barrier. Callers that barrier behind a long-latency rank-0
        operation (storage-marker commit, metadata write over a cloud
        backend) must pass the operation's own timeout here — otherwise
        waiting ranks raise a spurious TimeoutError at the store default
        while the operation is still legitimately in flight (ADVICE r3).
        """

    @abc.abstractmethod
    def all_gather_object(self, obj: Any) -> List[Any]:
        ...

    @abc.abstractmethod
    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        ...


class NoOpCoordinator(Coordinator):
    def get_rank(self) -> int:
        return 0

    def get_world_size(self) -> int:
        return 1

    def barrier(self, timeout_s: Optional[float] = None) -> None:
        pass

    def all_gather_object(self, obj: Any) -> List[Any]:
        return [obj]

    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        return obj


class StoreCoordinator(Coordinator):
    """Object collectives over a :class:`Store`.

    Every collective consumes one *generation* so keys never collide across
    successive operations; all processes must issue the same sequence of
    collectives (same discipline as any process group).

    **Key garbage collection.** A job taking snapshots every N steps for
    weeks must not grow the coordination service without bound (VERDICT r2
    weak #3), so each rank deletes its *own* keys once global progress
    proves no rank can still read them. The proof: ranks issue collectives
    sequentially, and in a barrier or all-gather at generation ``g`` every
    rank sets its own ``…/g/<rank>`` key only *after* finishing every
    operation of generations ``< g`` (including all reads). So the moment
    this rank has observed all world-size keys of generation ``g``, every
    key this rank wrote at generations ``< g`` has been read by everyone
    who ever will — it deletes them. Broadcast completion proves nothing
    by itself about non-source ranks, so receivers additionally *ack*
    each broadcast with a tiny per-generation key; the source collects
    acks lazily (non-blocking) at its next broadcast and deletes both its
    payload keys and the acks (VERDICT r3 weak #6 — a broadcast-only
    steady state, e.g. a restore(step=None) serving loop, must not grow
    the store). Whichever proof lands first wins: ack collection and
    barrier/gather progress both delete the same keys, and double-delete
    is a no-op. Steady state: O(keys-per-collective) live keys per rank —
    O(world) total — instead of O(operations x world).
    """

    def __init__(self, store: Store, rank: int, world_size: int,
                 timeout_s: float = _DEFAULT_TIMEOUT_S) -> None:
        self._store = store
        self._rank = rank
        self._world = world_size
        # Stamp the trace identity the moment a rank is known, so every
        # trace this process flushes is mergeable (telemetry/merge.py).
        tracing.set_identity(rank=rank)
        self._gen = 0
        self._timeout_s = timeout_s
        # (generation, key) for every key this rank wrote and has not yet
        # proven globally consumed.
        self._own_keys: List[tuple] = []
        # Generations at which this rank was a broadcast *source* and has
        # not yet observed every receiver's ack (oldest first).
        self._pending_bc: List[int] = []

    def _gc_through(self, proven_gen: int) -> None:
        """Delete own keys of generations < ``proven_gen`` (all ranks are
        proven past them); keep the rest pending."""
        keep = []
        for gen, key in self._own_keys:
            if gen < proven_gen:
                self._store.delete(key)
            else:
                keep.append((gen, key))
        self._own_keys = keep
        self._pending_bc = [g for g in self._pending_bc if g >= proven_gen]

    def get_rank(self) -> int:
        return self._rank

    def get_world_size(self) -> int:
        return self._world

    def _next_gen(self) -> int:
        self._gen += 1
        return self._gen

    def _set_chunked(self, key: str, payload: bytes, gen: int) -> None:
        if len(payload) <= _CHUNK:
            self._store.set(key, b"\x00" + payload)
            self._own_keys.append((gen, key))
        else:
            n = -(-len(payload) // _CHUNK)
            for i in range(n):
                part = f"{key}/part{i}"
                self._store.set(part, payload[i * _CHUNK:(i + 1) * _CHUNK])
                self._own_keys.append((gen, part))
            self._store.set(key, b"\x01" + str(n).encode())
            self._own_keys.append((gen, key))

    def _remaining(self, deadline: Optional[float]) -> float:
        if deadline is None:
            return self._timeout_s
        # Floor, don't clamp to zero: a zero budget would make backends
        # that check the deadline before the key (JaxStore's
        # blocking_key_value_get at 0 ms) time out even on a key that is
        # already published — and the caller would then blame a healthy
        # rank. The floor keeps "present key always wins" and bounds the
        # deadline overshoot at ~50 ms per remaining key.
        return max(0.05, deadline - time.monotonic())

    def _get_chunked(
        self, key: str, deadline: Optional[float] = None
    ) -> bytes:
        head = self._store.get(key, self._remaining(deadline))
        if head[:1] == b"\x00":
            return head[1:]
        n = int(head[1:].decode())
        return b"".join(
            self._store.get(f"{key}/part{i}", self._remaining(deadline))
            for i in range(n)
        )

    def _absent_ranks(self, key_fmt: str, first: int) -> List[int]:
        """``first`` plus every later rank whose key is absent *now* — a
        non-blocking sweep so a timeout error names EVERY straggler (at
        pod scale "ranks 17, 40-63" localizes the failure; "rank 17"
        alone does not). A false absent from a remote-store hiccup only
        over-names the report; the operation already failed."""
        missing = [first]
        for r in range(first + 1, self._world):
            if self._store.try_get(key_fmt.format(rank=r)) is None:
                missing.append(r)
        return missing

    @staticmethod
    def _fmt_ranks(ranks: List[int]) -> str:
        return format_rank_list(ranks)

    def barrier(self, timeout_s: Optional[float] = None) -> None:
        wait = self._timeout_s if timeout_s is None else timeout_s
        gen = self._next_gen()
        key = f"b/{gen}/{self._rank}"
        self._store.set(key, b"1")
        self._own_keys.append((gen, key))
        # One shared deadline for the whole barrier, not a fresh timeout
        # per rank: the caller's timeout bounds the OPERATION (a per-rank
        # budget would let the total wait grow to world x timeout), and
        # every rank that never arrives is named in the error instead of
        # surfacing as an opaque store-key timeout.
        deadline = time.monotonic() + wait
        wait_t0 = time.monotonic()
        try:
            for r in range(self._world):
                try:
                    self._store.get(f"b/{gen}/{r}", self._remaining(deadline))
                except TimeoutError:
                    missing = self._absent_ranks(f"b/{gen}/{{rank}}", r)
                    raise TimeoutError(
                        f"barrier (generation {gen}) timed out after "
                        f"{wait:g}s: {self._fmt_ranks(missing)} never arrived "
                        f"(observed by rank {self._rank} of {self._world}); "
                        f"likely crashed or stuck in storage IO."
                    ) from None
        finally:
            # Timed-out barriers observe too: a stall that ends in an
            # error is exactly the wait a dashboard must show.
            telemetry.record_coord_wait(
                "barrier", time.monotonic() - wait_t0
            )
        # Barrier-exit instant: every rank passes this point only after
        # the LAST rank arrived, so across ranks the same generation's
        # instants mark (approximately) one global wall-clock moment —
        # the clock-skew anchors telemetry/merge.py aligns traces with.
        tracing.instant("barrier_exit", gen=gen)
        self._gc_through(gen)

    def all_gather_object(self, obj: Any) -> List[Any]:
        gen = self._next_gen()
        self._set_chunked(
            f"ag/{gen}/{self._rank}", pickle.dumps(obj, protocol=4), gen
        )
        # Same shared-deadline discipline as barrier: self._timeout_s
        # bounds the whole gather — a fresh budget per rank key (or per
        # chunk part) would let the worst-case wait grow to world x
        # timeout.
        deadline = time.monotonic() + self._timeout_s
        out = []
        wait_t0 = time.monotonic()
        try:
            for r in range(self._world):
                try:
                    out.append(
                        pickle.loads(
                            self._get_chunked(f"ag/{gen}/{r}", deadline)
                        )
                    )
                except TimeoutError:
                    missing = self._absent_ranks(f"ag/{gen}/{{rank}}", r)
                    raise TimeoutError(
                        f"all_gather (generation {gen}) timed out after "
                        f"{self._timeout_s:g}s total: "
                        f"{self._fmt_ranks(missing)} never "
                        f"finished publishing (observed by rank "
                        f"{self._rank} of {self._world})."
                    ) from None
        finally:
            telemetry.record_coord_wait(
                "all_gather", time.monotonic() - wait_t0
            )
        self._gc_through(gen)
        return out

    def broadcast_object(self, obj: Any, src: int = 0) -> Any:
        gen = self._next_gen()
        if self._rank == src:
            self._collect_broadcast_acks()
            self._set_chunked(f"bc/{gen}", pickle.dumps(obj, protocol=4), gen)
            self._pending_bc.append(gen)
            # Bounded in-flight window: block on the oldest generation's
            # acks once too many are outstanding. Safe — receivers are
            # sequential and the pending payloads all exist, so every
            # receiver reaches (and acks) the oldest one without needing
            # anything further from this rank.
            while len(self._pending_bc) > _BC_WINDOW:
                self._collect_broadcast_acks(block_oldest=True)
            return obj
        self._prune_consumed_acks()
        deadline = time.monotonic() + self._timeout_s
        wait_t0 = time.monotonic()
        try:
            out = pickle.loads(self._get_chunked(f"bc/{gen}", deadline))
        except TimeoutError:
            raise TimeoutError(
                f"broadcast (generation {gen}) timed out after "
                f"{self._timeout_s:g}s total: source rank {src} never "
                f"finished publishing (receiving rank {self._rank} of "
                f"{self._world})."
            ) from None
        finally:
            telemetry.record_coord_wait(
                "broadcast", time.monotonic() - wait_t0
            )
        # Ack after the read completes: the source may delete the payload
        # keys the moment all acks exist. The ack is also tracked in
        # _own_keys so barrier/gather progress collects it if the source
        # never broadcasts again.
        ack = f"bcack/{gen}/{self._rank}"
        self._store.set(ack, b"1")
        self._own_keys.append((gen, ack))
        return out

    def _prune_consumed_acks(self) -> None:
        """Receiver-side bookkeeping GC: drop own ack entries whose store
        keys the source already deleted. Without this, a broadcast-only
        receiver loop grows ``_own_keys`` by one tuple per broadcast
        forever, then floods the store with an O(history) burst of no-op
        deletes at the next barrier/gather. Oldest first, stop at the
        first still-present ack — the source consumes acks in generation
        order, so later acks cannot be gone either. A false absent probe
        (remote-store hiccup) merely skips the later self-delete of a key
        the source deletes anyway."""
        while True:
            idx = next(
                (
                    i
                    for i, (_, k) in enumerate(self._own_keys)
                    if k.startswith("bcack/")
                ),
                None,
            )
            if idx is None or self._store.try_get(
                self._own_keys[idx][1]
            ) is not None:
                return
            self._own_keys.pop(idx)

    def _collect_broadcast_acks(self, block_oldest: bool = False) -> None:
        """Source-side GC of broadcast payload keys.

        Oldest pending generation first; stop at the first generation not
        fully acked — ranks issue collectives sequentially, so a receiver
        that has not acked generation ``g`` cannot have acked any later
        one, and checking further would waste non-blocking probes. With
        ``block_oldest`` the first generation is waited on (window
        overflow) rather than probed."""
        first = True
        while self._pending_bc:
            gen = self._pending_bc[0]
            acks = [
                f"bcack/{gen}/{r}"
                for r in range(self._world)
                if r != self._rank
            ]
            if block_oldest and first:
                deadline = time.monotonic() + self._timeout_s
                for a in acks:
                    try:
                        self._store.get(a, self._remaining(deadline))
                    except TimeoutError:
                        raise TimeoutError(
                            f"broadcast ack (generation {gen}) timed out "
                            f"after {self._timeout_s:g}s total: rank "
                            f"{a.rsplit('/', 1)[1]} never acknowledged "
                            f"(source rank {self._rank} of "
                            f"{self._world})."
                        ) from None
                first = False
            elif any(self._store.try_get(a) is None for a in acks):
                return
            for a in acks:
                self._store.delete(a)
            keep = []
            for g, key in self._own_keys:
                if g == gen:
                    self._store.delete(key)
                else:
                    keep.append((g, key))
            self._own_keys = keep
            self._pending_bc.pop(0)


def barrier_compat(coordinator: "Coordinator", timeout_s: float) -> None:
    """``coordinator.barrier(timeout_s=...)``, tolerating out-of-tree
    Coordinator implementations written against the pre-r4 ABC whose
    ``barrier(self)`` takes no timeout — they must degrade to their own
    default wait, not raise TypeError at the commit barrier after all
    the expensive storage work already succeeded."""
    import inspect

    try:
        params = inspect.signature(coordinator.barrier).parameters
        accepts = "timeout_s" in params or any(
            p.kind is p.VAR_KEYWORD for p in params.values()
        )
    except (ValueError, TypeError):
        accepts = False
    if accepts:
        coordinator.barrier(timeout_s=timeout_s)
    else:
        coordinator.barrier()


# Process-wide singleton: collective key generations must advance
# monotonically across *all* snapshot operations in a process — a fresh
# StoreCoordinator per take() would restart at generation 1 and collide
# with keys already present in the persistent coordination service.
_default_coordinator: Optional[Coordinator] = None
_default_coordinator_lock = threading.Lock()


def get_coordinator(coord: Optional[Coordinator] = None) -> Coordinator:
    """Resolve the coordinator: explicit > jax.distributed > single-process.

    Reference analog: PGWrapper's fallback to WORLD / no-op
    (pg_wrapper.py:24-29). The auto-resolved jax.distributed coordinator is
    a process-wide singleton so successive snapshot operations never reuse
    KV keys. Explicitly-passed coordinators are likewise expected to be
    long-lived (one per process, like a process group).
    """
    global _default_coordinator
    if coord is not None:
        return coord
    with _default_coordinator_lock:
        if _default_coordinator is not None:
            return _default_coordinator
        # Imported plainly: if JAX's internals move, this raises instead
        # of degrading a multi-host job to a world-size-1 coordinator.
        import jax
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            # jax.distributed not initialized — single-process. Not cached,
            # so a later jax.distributed.initialize() is still honored
            # (initialize() must precede the first *multi-process* snapshot
            # op, as with any process group).
            return NoOpCoordinator()
        # jax.distributed IS initialized: failures past this point must
        # raise, not silently degrade to a world-size-1 coordinator that
        # would corrupt multi-host snapshots.
        _default_coordinator = StoreCoordinator(
            store=JaxStore(),
            rank=jax.process_index(),
            world_size=jax.process_count(),
        )
        return _default_coordinator
