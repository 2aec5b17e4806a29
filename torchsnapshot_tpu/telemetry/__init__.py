"""snapstats: always-on metrics, per-snapshot flight recorder, and trace
analytics (beyond reference parity — SURVEY §5: "Tracing/profiling:
none").

Three layers, smallest first:

- **Metrics** (:mod:`.metrics`) — process-wide counters, gauges, and
  log-bucketed histograms, always recording, thread-safe, no deps.
  ``telemetry.snapshot()`` returns everything as plain data.
- **Exporters** (:mod:`.export`) — Prometheus textfile format (written
  atomically, with a matching parser) and structured JSON-lines. Env
  knobs ``TPUSNAPSHOT_METRICS_TEXTFILE`` / ``TPUSNAPSHOT_TELEMETRY_JSONL``
  auto-export after every take/restore.
- **Flight recorder** (:mod:`.report`) — every ``Snapshot.take`` gathers
  per-rank summaries at commit time and writes a ``.report.json`` beside
  the manifest; ``restore`` writes a rank-local report with the
  read/consume/assemble breakdown. ``python -m torchsnapshot_tpu.inspect
  <path> --report`` renders it.
- **Trace analytics** (:mod:`.summarize`) —
  ``python -m torchsnapshot_tpu.telemetry.summarize <trace.json>`` folds
  a Chrome trace into a per-phase table and names the dominant phase.
- **Live progress / snapwatch** (:mod:`.progress`, :mod:`.watch`) —
  in-flight per-rank progress records (phase, bytes, heartbeat) to a
  local statusfile and ``.progress/<take_id>/<rank>`` storage objects;
  ``python -m torchsnapshot_tpu.telemetry.watch <path>`` renders them
  and flags stale-heartbeat stragglers.
- **Cross-rank merge** (:mod:`.merge`) — N per-rank traces onto one
  skew-corrected clock, with the cross-rank critical path.
- **Doctor** (:mod:`.doctor`) — structured anomaly findings (rule id +
  evidence + remediation) from flight reports; ``inspect --doctor``.
- **Ledger / snapledger** (:mod:`.ledger`) — durable cross-take record:
  every committed take/restore appends a checksummed digest to
  ``<root>/.telemetry/ledger.jsonl`` (rank-0-only, crash-tolerant,
  torn-tail-skipping parser; survives delete/prune/reconcile).
- **Goodput** (:mod:`.goodput`) — train-vs-checkpoint wall-time
  attribution: call ``goodput.step()`` once per train step; the
  library reports its own blocking automatically.
- **Timeline** (:mod:`.timeline`) —
  ``python -m torchsnapshot_tpu.telemetry.timeline <base>`` renders
  per-step trends from the ledger and runs a median/MAD regression
  sentinel; exit 0/1/2 for CI.
- **Runtime sampler / snapscope** (:mod:`.sampler`) — a crash-isolated
  background thread snapshotting live runtime state (hot-tier drain
  queue/at-risk bytes/host occupancy, scheduler budget, goodput) into
  a bounded ring + ``rank<N>.scope.jsonl`` statusfiles + optional
  ``.scope/rank<N>`` storage objects.
- **SLO engine** (:mod:`.slo`) — declarative objectives (durability
  lag, checkpoint overhead, restore seconds, take GB/s floor) with
  multi-window burn rates over the ledger plus live sampler rules
  (``durability-lag-above-budget``, ``drain-backlog-growing``,
  ``stranded-drains``); CI exit-code contract like ``timeline``'s.
- **Ops view** (:mod:`.ops`) —
  ``python -m torchsnapshot_tpu.telemetry.ops <path>`` merges live
  progress, sampler state, SLO status, and doctor findings into one
  per-rank operational display (dir and storage-URL modes).

NOTE: :mod:`.report` is deliberately NOT imported here — it depends on
``io_types``, which itself records metrics through this package; keeping
the package root import-light breaks the cycle. Import it explicitly
(``from torchsnapshot_tpu.telemetry import report``).
"""

import time
from typing import Any, Dict, Optional

from . import metrics as _m
from . import goodput  # noqa: F401  (telemetry.goodput.step() is the train-loop hook)
from .metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    diff_snapshots,
)

__all__ = [
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "counter",
    "gauge",
    "goodput",
    "histogram",
    "snapshot",
    "reset",
    "diff_snapshots",
    "record_storage_op",
    "record_scheduler_op",
    "record_coord_wait",
    "timer",
]


def counter(name: str, **labels: str) -> Counter:
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels: str) -> Gauge:
    return REGISTRY.gauge(name, **labels)


def histogram(name: str, **labels: str) -> Histogram:
    return REGISTRY.histogram(name, **labels)


def snapshot() -> Dict[str, Any]:
    """Every metric's current value as plain (JSON-able) data — the
    programmatic export API."""
    return REGISTRY.snapshot()


def reset() -> None:
    """Drop all metrics (test isolation; never called by library code)."""
    REGISTRY.reset()


class timer:
    """``with telemetry.timer() as t: ...`` then ``t.elapsed_s``."""

    __slots__ = ("t0", "elapsed_s")

    def __enter__(self) -> "timer":
        self.t0 = time.monotonic()
        self.elapsed_s = 0.0
        return self

    def __exit__(self, *exc: Any) -> None:
        self.elapsed_s = time.monotonic() - self.t0


# ----------------------------------------------------- recording shorthands
#
# One-call helpers for the instrumented seams, so call sites stay one
# line and the metric names live in exactly one place (metrics.py).


def record_storage_op(
    backend: str, op: str, seconds: float, nbytes: Optional[int] = None
) -> None:
    """One storage-plugin op completed (fs/memory/gcs/s3 write/read/...)."""
    REGISTRY.histogram(_m.STORAGE_OP_SECONDS, backend=backend, op=op).observe(
        seconds
    )
    if nbytes is not None:
        REGISTRY.histogram(
            _m.STORAGE_OP_BYTES, backend=backend, op=op
        ).observe(nbytes)


def record_scheduler_op(op: str, seconds: float, nbytes: int) -> None:
    """One pipelined request op completed (stage/write/read/consume)."""
    REGISTRY.histogram(_m.SCHED_OP_SECONDS, op=op).observe(seconds)
    REGISTRY.histogram(_m.SCHED_OP_BYTES, op=op).observe(nbytes)


def record_coord_wait(op: str, seconds: float) -> None:
    """One coordinator collective completed (barrier/all_gather/broadcast)."""
    REGISTRY.histogram(_m.COORD_WAIT_SECONDS, op=op).observe(seconds)
