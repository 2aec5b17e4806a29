"""URL → StoragePlugin dispatch.

TPU-native analog of reference torchsnapshot/storage_plugin.py:16-60.
Protocols: ``fs`` (default when no ``://`` present), ``memory``, ``gs``,
``s3``, ``snapserve`` (the read-plane client,
``snapserve://host:port/<backend-url>``); unknown protocols resolve
through the ``storage_plugins`` Python
entry-point group so third-party backends can register themselves
(reference storage_plugin.py:43-58).

Also home to :class:`RefRouterPlugin`, the storage-side half of
incremental snapshots: manifest entries whose payload lives in a BASE
snapshot (unchanged since that take — never rewritten) resolve through
``@base<N>/<location>`` paths that the router forwards to the base
snapshot's own storage root.
"""

import logging
from importlib import metadata as importlib_metadata
from typing import Callable, Dict, List, Optional, Tuple

from .io_types import IOReq, RetryingStoragePlugin, StoragePlugin
from .storage_plugins.fs import FSStoragePlugin
from .storage_plugins.memory import MemoryStoragePlugin

logger = logging.getLogger(__name__)

# Shared in-memory "buckets" keyed by root so that memory://foo resolves to
# the same store across plugin instances within a process (tests, async
# staging targets).
_MEMORY_STORES: Dict[str, Dict[str, bytes]] = {}

# Fault-injection seam (torchsnapshot_tpu.faultline): when set, every
# resolved backend is passed through this wrapper BEFORE the retry layer,
# so injected transient failures exercise the real retry policy while an
# injected crash (a BaseException) rips straight through it — the same
# layering a real backend failure or process death would see. Process-
# global on purpose: take/finalize/prune each resolve their own plugin
# instance, and one controller must observe them all as one op stream.
_PLUGIN_WRAP_HOOK: Optional[Callable[[StoragePlugin, str], StoragePlugin]] = None


def set_plugin_wrap_hook(hook):
    """Install (or, with None, clear) the plugin wrapper applied to every
    backend ``url_to_storage_plugin`` resolves; returns the previous hook
    so callers can restore it."""
    global _PLUGIN_WRAP_HOOK
    prev = _PLUGIN_WRAP_HOOK
    _PLUGIN_WRAP_HOOK = hook
    return prev


def url_to_storage_plugin(url_path: str) -> StoragePlugin:
    """Resolve a URL to its backend, wrapped with the retry policy (every
    storage op — payloads, metadata commit, markers, deletes — retries
    transient failures; see io_types.retry_storage_op)."""
    plugin = _resolve_plugin(url_path)
    if _PLUGIN_WRAP_HOOK is not None:
        plugin = _PLUGIN_WRAP_HOOK(plugin, url_path)
    return RetryingStoragePlugin(plugin)


def _resolve_plugin(url_path: str) -> StoragePlugin:
    if "://" in url_path:
        protocol, path = url_path.split("://", 1)
        if protocol == "":
            protocol = "fs"
    else:
        protocol, path = "fs", url_path

    if protocol == "fs":
        return FSStoragePlugin(root=path)
    if protocol == "memory":
        # Hierarchical, like a real object store: the first path segment
        # names the bucket, the rest is a key prefix within it — so
        # memory://run and memory://run/step-0 share one bucket and the
        # base root can enumerate the step's objects.
        bucket, _, prefix = path.partition("/")
        store = _MEMORY_STORES.setdefault(bucket, {})
        return MemoryStoragePlugin(store=store, prefix=prefix)
    if protocol == "snapserve":
        # Disaggregated read plane (snapserve/): reads go through the
        # caching read service at host:port, everything else straight
        # to the embedded backend URL; unreachable servers degrade to
        # direct backend reads (counted, never an error).
        from .snapserve.client import SnapServePlugin

        return SnapServePlugin(path)
    if protocol == "gs":
        from .storage_plugins.gcs import GCSStoragePlugin

        return GCSStoragePlugin(root=path)
    if protocol == "s3":
        from .storage_plugins.s3 import S3StoragePlugin

        return S3StoragePlugin(root=path)

    # Third-party plugins via entry points.
    try:
        eps = importlib_metadata.entry_points()
        if hasattr(eps, "select"):
            group = list(eps.select(group="storage_plugins"))
        else:  # pragma: no cover
            group = list(eps.get("storage_plugins", []))
    except Exception:
        # Broken entry-point metadata in some unrelated package must not
        # mask the actionable "unsupported protocol" error below — but it
        # must be visible, or a mispackaged environment looks identical
        # to a missing plugin.
        logger.warning(
            f"Enumerating storage_plugins entry points for protocol "
            f"{protocol!r} failed",
            exc_info=True,
        )
        group = []
    for ep in group:
        if ep.name == protocol:
            # The plugin IS installed: a load()/constructor failure is
            # the real, actionable error — propagate it instead of
            # demoting it to "unsupported protocol".
            return ep.load()(path)
    raise RuntimeError(f"Unsupported protocol: {protocol}")


# --------------------------------------------------------- incremental refs
#
# Location namespace: a payload location beginning with "@base<N>/" lives
# under the snapshot root named by SnapshotMetadata.base_paths[N] instead
# of the snapshot's own root. Real storage locations never begin with "@"
# (they begin with "<rank>/", "replicated/", "chunked/", or ".completed/"),
# so the marker cannot collide.

_REF_MARKER = "@base"


def make_ref_location(base_idx: int, location: str) -> str:
    return f"{_REF_MARKER}{base_idx}/{location}"


def parse_ref_location(path: str) -> Optional[Tuple[int, str]]:
    """``"@base<N>/<rest>"`` → ``(N, rest)``; None for ordinary paths.
    ``N`` must be exactly what :func:`make_ref_location` emits — plain
    digits. ``int()`` alone would accept "-1"/"+1"/whitespace, and a
    negative index would wrap through Python list indexing into the
    WRONG base root instead of tripping the corrupt-metadata guard."""
    if not path.startswith(_REF_MARKER):
        return None
    head, sep, rest = path.partition("/")
    if not sep:
        return None
    digits = head[len(_REF_MARKER):]
    # ASCII digits only: isdigit() alone admits Unicode digit-likes
    # (e.g. "²") that int() then rejects with an uncaught ValueError —
    # in exactly the corrupt-input case this parse exists to neutralize.
    if not (digits.isascii() and digits.isdigit()):
        return None
    return int(digits), rest


def is_ref_location(path: str) -> bool:
    return parse_ref_location(path) is not None


def _parent_url(url: str) -> Optional[str]:
    """The parent "directory" of a snapshot URL, or None when there is
    none to speak of (e.g. ``memory://bucket`` with a rootless path)."""
    trimmed = url.rstrip("/")
    if "://" in trimmed:
        scheme, _, rest = trimmed.partition("://")
        if "/" not in rest:
            return None
        head, _, _ = rest.rpartition("/")
        return f"{scheme}://{head}"
    if "/" not in trimmed:
        return None
    return trimmed.rpartition("/")[0]


def encode_base_ref(base_path: str, own_path: str) -> str:
    """Record a base-snapshot reference portably.

    Siblings (same parent directory) are recorded relative
    (``"rel:<name>"``) so moving/renaming the whole snapshot family —
    the layout CheckpointManager produces — never breaks the chain;
    anything else is recorded absolute (``"abs:<url>"``).
    """
    bp, op = base_path.rstrip("/"), own_path.rstrip("/")
    b_parent, o_parent = _parent_url(bp), _parent_url(op)
    if b_parent is not None and b_parent == o_parent:
        return "rel:" + bp.rsplit("/", 1)[1]
    return "abs:" + bp


def resolve_base_ref(ref: str, own_path: str) -> str:
    """Resolve an encoded base reference against this snapshot's path."""
    if ref.startswith("rel:"):
        parent = _parent_url(own_path.rstrip("/"))
        if parent is None:
            raise ValueError(
                f"Cannot resolve relative base reference {ref!r}: snapshot "
                f"path {own_path!r} has no parent directory"
            )
        return f"{parent}/{ref[4:]}"
    if ref.startswith("abs:"):
        return ref[4:]
    raise ValueError(f"Malformed base reference: {ref!r}")


class RefRouterPlugin(StoragePlugin):
    """Routes ``@base<N>/…`` paths to base-snapshot storage roots.

    Wraps a snapshot's primary plugin; ordinary paths pass through
    untouched. Base plugins open lazily on first touch and close with
    the router. Writes and deletes against ``@base`` paths are refused —
    a snapshot never mutates objects another snapshot owns (the
    back-link markers written into a base during take go through an
    explicitly-opened plugin, not this router).
    """

    def __init__(self, inner: StoragePlugin) -> None:
        self._inner = inner
        self._base_urls: List[str] = []
        self._base_plugins: Dict[int, StoragePlugin] = {}
        self.max_write_concurrency = inner.max_write_concurrency
        self.max_read_concurrency = inner.max_read_concurrency

    def attach_bases(self, base_urls: List[str]) -> None:
        self._base_urls = list(base_urls)

    def _route(self, path: str) -> Tuple[StoragePlugin, str]:
        parsed = parse_ref_location(path)
        if parsed is None:
            return self._inner, path
        idx, rest = parsed
        if idx >= len(self._base_urls):
            raise RuntimeError(
                f"Manifest references base snapshot #{idx} but metadata "
                f"records only {len(self._base_urls)} base path(s) — "
                f"corrupt or truncated metadata"
            )
        plugin = self._base_plugins.get(idx)
        if plugin is None:
            plugin = url_to_storage_plugin(self._base_urls[idx])
            self._base_plugins[idx] = plugin
        return plugin, rest

    async def write(self, io_req: IOReq) -> None:
        if is_ref_location(io_req.path):
            raise RuntimeError(
                f"Refusing to write into a base snapshot: {io_req.path}"
            )
        await self._inner.write(io_req)

    async def read(self, io_req: IOReq) -> None:
        plugin, path = self._route(io_req.path)
        if plugin is self._inner:
            await plugin.read(io_req)
            return
        routed = IOReq(
            path=path, buf=io_req.buf, byte_range=io_req.byte_range,
            into=io_req.into,
        )
        await plugin.read(routed)
        io_req.data = routed.data

    async def delete(self, path: str) -> None:
        if is_ref_location(path):
            raise RuntimeError(
                f"Refusing to delete an object owned by a base snapshot: "
                f"{path} (delete the base snapshot itself, after its "
                f"referencing snapshots are gone)"
            )
        await self._inner.delete(path)

    async def list_prefix(self, prefix: str):
        # Enumeration stays within the snapshot's OWN prefix: sweeps and
        # ref checks must never wander into a base root.
        return await self._inner.list_prefix(prefix)

    async def object_age_s(self, path: str) -> Optional[float]:
        plugin, p = self._route(path)
        return await plugin.object_age_s(p)

    async def object_size_bytes(self, path: str) -> Optional[int]:
        plugin, p = self._route(path)
        return await plugin.object_size_bytes(p)

    def close(self) -> None:
        for plugin in self._base_plugins.values():
            try:
                plugin.close()
            except Exception:  # pragma: no cover - best-effort teardown
                logger.warning("base plugin close failed", exc_info=True)
        self._base_plugins.clear()
        self._inner.close()
