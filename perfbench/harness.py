"""One run of one cell: set-up, the measured window, the comparison that
decides ``correct``, and the result line.

The job (``jobs/<name>.py``, named by the configuration) makes and steps
the state that is saved; the loop kinds (``loops/<kind>.py``) drive the
window through the services of :class:`Run`; the per-layer readers
(``layers/<metric>.py``) turn what the loop observed into one number
each. None is known here by name.

``correct`` reads no clock: it is decided by exact comparisons (restored
bits against the sums pinned at save time, steps resolved against steps
saved) and by the count of operations that raised. A slow disk gives a
large ``save_durable_s``, never ``false``.
"""

import contextlib
import gc
import json
import logging
import os
import shutil
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

from perfbench import manifest as manifest_mod
from perfbench import reference, roots, spans, xplane

_HOST_FALLBACK_MARK = "falling back to host staging"


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


class LibraryWarnings(logging.Handler):
    """Collects the package's WARNING+ records: the capture route of an
    async save is only ever reported there (copy of ``chip_smoke.py``'s
    handler)."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())

    def fallbacks(self) -> int:
        return sum(1 for m in self.messages if _HOST_FALLBACK_MARK in m)


class CompileTally:
    """Backend compiles seen through ``jax.monitoring``: the window's
    count has to be 0 (copy of ``chip_smoke.py``'s tally)."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, name: str, **_) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def fresh_compiles(self) -> int:
        """Programs built by the compiler, not loaded from the cache."""
        return self.compiles - self.cache_hits


class Run:
    """What a loop kind works with. One per process."""

    def __init__(
        self,
        cell: manifest_mod.Cell,
        seed: int,
        seconds: float,
        trace: bool,
        root: str,
        out_dir: str,
        devices: List[Any],
        started_at: float,
    ) -> None:
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.root = root
        self.out_dir = out_dir
        self.devices = devices
        self.started_at = started_at
        self.job = manifest_mod.load_module(cell.job_path).make_job(
            cell.config, devices, seed
        )
        self.checksum = reference.make_checksum_fn()
        self.leaf_names = reference.leaf_names(self.job.shapes)
        self.log = LibraryWarnings()
        self.tally = CompileTally()
        self.attempted = 0
        self.failed = 0
        self.compared: Dict[str, Dict[str, float]] = {}
        self.metrics: Dict[str, float] = {}
        # What readers read: the loop's own observations, the probes,
        # the program's spans and the device trace.
        self.obs: Dict[str, Any] = {
            "state_bytes": self.job.state_bytes,
        }
        self.window_began: Optional[float] = None
        self.window_s: Optional[float] = None
        self.compiles_before_window = 0
        self.memory_peak_bytes = 0
        self.device_trace: Optional[xplane.DeviceTrace] = None
        self._spans_path = os.path.join(out_dir, f"spans-{os.getpid()}.json")
        self._profile_dir = os.path.join(out_dir, f"profile-{os.getpid()}")
        self._diagnoses: List[Dict[str, Any]] = []

    # ------------------------------------------------------------ set-up

    def mark(self, what: str) -> None:
        """Says how far the process has come: where set-up time goes."""
        say(f"{time.monotonic() - self.started_at:8.2f} s  {what}")

    def note(self, name: str):
        """A host annotation on the profiler's clock; free when no
        profile is being taken."""
        import jax

        return jax.profiler.TraceAnnotation(xplane.ANNOTATION_PREFIX + name)

    def take_probes(self) -> None:
        """Only a traced run probes the links: no end-to-end metric
        reads a probe, and every run pays its set-up."""
        if not self.trace:
            return
        from perfbench import probes

        scale = float(self.cell.traffic.get("probe_scale", 1.0))
        self.obs["probes"] = probes.run_probes(self.devices, self.root, scale)
        say(f"probes: {self.obs['probes']}")

    # ------------------------------------------------------------ window

    def open_window(self) -> float:
        """Set-up ends here. Returns the window's start."""
        from torchsnapshot_tpu import tracing

        gc.collect()
        self.log.messages.clear()
        self.compiles_before_window = self.tally.fresh_compiles()
        if self.trace:
            tracing.enable(self._spans_path)
            self.device_trace = xplane.DeviceTrace(
                self._profile_dir, self.cell.chips
            )
        now = time.monotonic()
        self.metrics["setup_s"] = now - self.started_at
        self.window_began = now
        return now

    def window_open(self, now: float) -> bool:
        return now - self.window_began < self.seconds

    def close_window(self) -> float:
        now = time.monotonic()
        self.window_s = now - self.window_began
        self.obs["window_s"] = self.window_s
        return now

    def after_window(self) -> None:
        """Call once every pending operation of the window has been
        waited for: ends the captures and reads the device's peak before
        the comparison puts anything of its own on the chip."""
        from torchsnapshot_tpu import tracing

        if self.device_trace is not None and self.device_trace.running:
            self.device_trace.stop(time.monotonic())
        if self.trace:
            tracing.disable()
        self.obs["capture_fallbacks"] = self.log.fallbacks()
        self.obs["compiles_in_window"] = (
            self.tally.fresh_compiles() - self.compiles_before_window
        )
        self.memory_peak_bytes = self._peak_now()

    # -------------------------------------------------------- comparison

    def compare(self, name: str, value: float, limit: float) -> None:
        """One number compared; the run is correct only if every value is
        within its limit."""
        self.compared[name] = {"value": value, "limit": limit}

    def diagnose(self, **facts: Any) -> None:
        """Says what went wrong, where: on stdout before the result line
        and in a file under the output directory."""
        facts = {
            "cell": self.cell.name,
            "seed": self.seed,
            **facts,
            "capture_fallbacks": self.log.fallbacks(),
            "peak_bytes_in_use": self._peak_now(),
            "free_bytes_under_root": _free_bytes(self.root),
        }
        self._diagnoses.append(facts)
        print("[perfbench] MISMATCH " + json.dumps(facts, default=str), flush=True)

    def _peak_now(self) -> int:
        """``peak_bytes_in_use`` of the fullest chip; 0 where the backend
        reports none."""
        peaks = [
            int(stats["peak_bytes_in_use"])
            for stats in (device.memory_stats() for device in self.devices)
            if stats and "peak_bytes_in_use" in stats
        ]
        return max(peaks, default=0)

    def compare_sums(self, what: str, step: int, pinned, got) -> int:
        """Sums against those pinned at save time; returns how many
        leaves differ, each one diagnosed."""
        import numpy as np

        differing = reference.differing_leaves(
            self.leaf_names, np.asarray(pinned), np.asarray(got)
        )
        for entry in differing:
            self.diagnose(comparison=what, step=step, **entry)
        return len(differing)

    def compare_copies(self, what: str, step: int, pinned, pending) -> int:
        """Every copy of every leaf (``reference.make_copy_checksum_fn``)
        against the sums pinned at save time; returns how many leaves
        have a copy that differs, each such copy diagnosed."""
        import numpy as np

        differing = reference.differing_copies(
            self.leaf_names, np.asarray(pinned), reference.copy_sums(pending)
        )
        for entry in differing:
            self.diagnose(comparison=what, step=step, **entry)
        return len({entry["leaf"] for entry in differing})

    def compare_restored(self, what: str, step: int, pinned, restored_tree) -> int:
        return self.compare_sums(what, step, pinned, self.checksum(restored_tree))

    # ------------------------------------------------------------ result

    def finish(self) -> Dict[str, Any]:
        """The result line's object. ``compared`` comes last."""
        self.compare("operations_failed", self.failed, 0)
        correct = self.attempted > 0 and all(
            c["value"] <= c["limit"] for c in self.compared.values()
        )
        device = self.devices[0]
        import jax

        device_doc: Dict[str, Any] = {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": self.memory_peak_bytes,
        }
        line: Dict[str, Any] = {
            "correct": bool(correct),
            "attempted": self.attempted,
            "failed": self.failed,
        }
        if self.trace:
            line["metrics"], breakdown = self._read_trace(device_doc)
        else:
            breakdown = None
            line["metrics"] = {
                m["name"]: {"value": self.metrics[m["name"]], "unit": m["unit"]}
                for m in self.cell.end_to_end
                if m["name"] in self.metrics
            }
        line["device"] = device_doc
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["info"] = {
            "window_s": self.window_s,
            "compiles_in_window": self.obs.get("compiles_in_window"),
            "capture_fallbacks": self.obs.get("capture_fallbacks"),
            **self.obs.get("info", {}),
        }
        line["compared"] = self.compared
        if self._diagnoses:
            path = os.path.join(
                self.out_dir, f"diagnosis-{self.cell.name}-{self.seed}.json"
            )
            with open(path, "w") as f:
                json.dump(self._diagnoses, f, indent=1, default=str)
        return line

    def _read_trace(self, device_doc: Dict[str, Any]):
        """The per-layer metrics and the breakdown of a traced run."""
        breakdown = None
        if os.path.exists(self._spans_path):
            self.obs["spans"] = spans.read_spans(self._spans_path)
            os.remove(self._spans_path)
        reduced = self.device_trace.reduce() if self.device_trace else None
        shutil.rmtree(self._profile_dir, ignore_errors=True)
        if reduced is not None:
            self.obs["device"] = reduced
            device_doc["busy_s"] = reduced["busy_s"]
            device_doc["window_s"] = reduced["window_s"]
            breakdown = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
        metrics = {}
        for m in self.cell.per_layer:
            reader = manifest_mod.load_module(self.cell.reader_paths[m["name"]])
            value = reader.read(self.obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        return metrics, breakdown


def _free_bytes(path: str) -> Optional[int]:
    with contextlib.suppress(OSError):
        return shutil.disk_usage(path).free
    return None


def run_cell(
    cell: manifest_mod.Cell,
    seed: int,
    seconds: float,
    trace: bool,
    devices: List[Any],
    started_at: float,
    out_dir: str,
    roots_parent: Optional[str] = None,
) -> Dict[str, Any]:
    """Runs the cell once on ``devices`` and returns the result line's
    object. Everything but the look for a chip: the tests come in here."""
    os.makedirs(out_dir, exist_ok=True)
    library = logging.getLogger("torchsnapshot_tpu")
    with roots.run_root(roots_parent) as root:
        run = Run(cell, seed, seconds, trace, root, out_dir, devices, started_at)
        library.addHandler(run.log)
        try:
            loop = manifest_mod.load_module(cell.loop_path)
            try:
                loop.run(run)
            except Exception as e:  # a run that raises is a failed run, said aloud
                run.failed += 1
                run.attempted = max(run.attempted, 1)
                run.diagnose(
                    comparison="the loop raised",
                    error=repr(e),
                    traceback=traceback.format_exc()[-3000:],
                )
            return run.finish()
        finally:
            library.removeHandler(run.log)
            from torchsnapshot_tpu import tracing

            if tracing.enabled():
                tracing.disable()


def print_result(line: Dict[str, Any]) -> None:
    """Each number compared beside its limit as the last lines of
    standard error, then the one JSON object as the last line of
    standard output."""
    for name, c in line["compared"].items():
        print(
            f"[perfbench] compared {name}: value {c['value']} limit {c['limit']}",
            file=sys.stderr,
            flush=True,
        )
    print(json.dumps(line), flush=True)
