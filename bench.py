"""Snapshot throughput benchmark.

TPU-native analog of the reference DDP benchmark
(reference benchmarks/ddp/main.py:38-70): a synthetic model of N large
parameters is snapshotted to local storage and timed. The reference's
single-accelerator number is 0.44 GB/s (Snapshot.take, 1 GPU of a
p4d.24xlarge against FSx Lustre — BASELINE.md); `vs_baseline` is measured
GB/s over that.

Prints exactly ONE JSON line:
  {"metric": "snapshot_take_GBps", "value": N, "unit": "GB/s",
   "vs_baseline": N/0.44, "d2h_ceiling_GBps": ..., "take_vs_ceiling": ...,
   "bench_bytes": ..., "async_stall_s": ..., "async_stall_pct": ...,
   "restore_GBps": ...}

Device-link bandwidth differs between machines and runs. Two consequences:

- The benchmark CALIBRATES its payload size against a D2H probe so it
  finishes in bounded wall-clock at any link speed (an explicitly set
  TPUSNAPSHOT_BENCH_BYTES pins the size instead).
- The JSON also reports the probe ceiling and take/ceiling — the ratio
  that is comparable across runs (VERDICT r1 #3 asks for take >= ~85% of
  the concurrently measured ceiling).

**Certification floor (round 3).** A measurement on a toy payload is not
evidence at scale. The bench refuses to silently certify below a floor:
if calibration would size the payload under ~1 GiB, it RE-calibrates
(fresh probe + a 100 MiB end-to-end sample) until the recalibration
budget runs out; if the floor still doesn't fit the remaining time
budget, it runs FEWER full-size runs (3 -> 1) before it shrinks the
payload — and if it must shrink below the floor (or must cut the restore
below its 0.5 GiB floor), the JSON carries ``"degraded": true``.

**Round-4 additions** (VERDICT r3 #1/#3/#8):

- The restore is re-timed not only on probe disagreement but whenever
  restore/ceiling misses 0.5 with stable probes; if the ratio still
  misses after retries the JSON carries ``"restore_uncertified": true``
  (which also sets ``degraded``), and every timed restore dumps a
  per-phase span breakdown (read/consume/assemble) to stderr + the JSON
  so a slow link is distinguishable from a code stall post-hoc.
- At-or-above the floor, the payload includes one 640 MiB parameter:
  chunked D2H staging, ONE large storage object, and the concurrent
  ranged-sub-read reassembly on restore are inside the certified loop.
- A subprocess pinned to the CPU platform runs the sharded-entry
  save/restore with >512 MiB shards (subdivided chunks) on an
  8-virtual-device CPU mesh and its timings land under
  ``"sharded_cpu"`` — path coverage at scale, explicitly not a device
  number. The payload clamp is 8 GiB.

**Round-5 hardening (VERDICT r4 #1): a summary is always printed.**
``TPUSNAPSHOT_BENCH_TOTAL_BUDGET_S`` is a HARD deadline, enforced twice
over:

- every phase records its results into a shared partial-results dict
  the moment they exist, and checks the deadline before starting more
  work (raising an internal abort that still emits the summary);
- a supervisor thread is the backstop for a phase stuck inside one
  blocking call: at the deadline it emits the summary JSON built from
  whatever completed, flushes, and exits.

Either way stdout carries exactly one parsed JSON line with
``degraded: true`` and an ``"abort"`` reason when the run was cut short
(``abort: null`` on a clean run).

**Exit code.** 0 only when the run completed and every section that ran
succeeded. An abort (deadline or exception) or a section that ran and
failed (``"ok": false`` without a ``"skipped"`` reason; listed under
``"failed_sections"``) exits 1 — after the summary line is printed.

**One process per chip.** This process holds the chip once it touches
JAX, so nothing it starts afterwards may need one: the in-situ stall
loop runs in this process, and the CPU-mesh sub-benches and hot-tier
peers are children pinned to ``JAX_PLATFORMS=cpu``.

Test hook: ``TPUSNAPSHOT_BENCH_THROTTLE_GBPS`` wraps every storage
plugin the bench touches with a token-rate throttle so the deadline
path is provable on CPU (tests/test_bench_deadline.py).

Env knobs:
  TPUSNAPSHOT_BENCH_BYTES          total parameter bytes (default:
                                   calibrated to ~45 s of take per run,
                                   clamped to [64 MiB, 2 GiB]; the
                                   payload floor below raises the lower
                                   clamp when the link can carry it)
  TPUSNAPSHOT_BENCH_FLOOR_BYTES    certification floor (default 1 GiB):
                                   below this payload the JSON is marked
                                   degraded
  TPUSNAPSHOT_BENCH_RESTORE_FLOOR_BYTES
                                   restore certification floor (default
                                   512 MiB)
  TPUSNAPSHOT_BENCH_RECAL_BUDGET_S wall-clock allowed for waiting out a
                                   collapsed link via re-calibration
                                   (default 240 s)
  TPUSNAPSHOT_BENCH_TOTAL_BUDGET_S HARD wall-clock deadline for the
                                   whole bench run (default 1200 s): the
                                   summary JSON is on stdout by this
                                   time, whatever the link does;
                                   floor-sized runs are only attempted
                                   while they fit in it
  TPUSNAPSHOT_BENCH_THROTTLE_GBPS  test hook: throttle all storage IO to
                                   this rate (simulates a slow link;
                                   used by the deadline tests)
  TPUSNAPSHOT_BENCH_RESTORE_BYTES  bytes restored in the restore timing
                                   (default: max(bench_bytes/4, restore
                                   floor), shrunk when the take budget
                                   below was exhausted — restore is gated
                                   by sustained H2D)
  TPUSNAPSHOT_BENCH_TAKE_BUDGET_S  soft cumulative budget for the timed
                                   take runs (default: what remains of
                                   the total budget after a restore
                                   reserve): when tenancy degrades after
                                   calibration, remaining runs are
                                   skipped and the async/restore payloads
                                   shrink so an external timeout is not
                                   blown
  TPUSNAPSHOT_BENCH_DIR            target directory (default: fresh tmpdir)
"""

import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from torchsnapshot_tpu import Snapshot  # noqa: E402
from torchsnapshot_tpu.models.ddp_synthetic import SyntheticModel  # noqa: E402
from torchsnapshot_tpu.ops.transfer import parallel_device_get  # noqa: E402

_REFERENCE_SINGLE_ACCEL_GBPS = 0.44
_TARGET_TAKE_SECONDS = 45.0

# ---------------------------------------------------------------- deadline
# Shared partial-results state: phases record into _RESULTS the moment a
# quantity exists, so the summary JSON can be assembled at ANY point —
# by the body on clean completion or abort, or by the supervisor thread
# when a phase is stuck inside one blocking call at the hard deadline.
_RESULTS: dict = {}
_PHASE = ["startup"]
_BENCH_START = [0.0]
_HARD_DEADLINE = [float("inf")]
_EMITTED = threading.Event()


class _HardDeadline(Exception):
    """Raised by phase gates when the remaining budget cannot carry the
    next piece of work; the body's handler emits the summary, and the
    process then exits 1 (an aborted run is not a successful one)."""


def _phase(name: str) -> None:
    _mem_section_begin(name)
    _PHASE[0] = name
    print(
        f"[bench] phase {name} "
        f"({time.monotonic() - _BENCH_START[0]:.0f}s elapsed)",
        file=sys.stderr,
    )


# Per-section host-memory accounting (snapmem satellite): every _phase
# boundary closes the previous section's memwatch window and opens a
# new one, so the BENCH JSON carries each section's domain high-waters
# plus the process peak RSS — a restore that quietly doubled the
# staging pool shows up in the artifact, not just on the host graph.
_MEM_SECTION: list = [None]  # (name, memwatch window token, peak at start)


def _peak_rss_bytes():
    """Lifetime peak RSS via getrusage; None off-POSIX."""
    try:
        import resource

        v = int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    except Exception:  # snapcheck: disable=swallowed-exception -- resource module is POSIX-only
        return None
    # Linux reports KiB; macOS reports bytes. Treat small values as KiB.
    return v if v > (1 << 32) else v * 1024


def _mem_section_begin(name: str) -> None:
    _mem_section_end()
    try:
        from torchsnapshot_tpu.telemetry import memwatch

        token = memwatch.window_begin()
    except Exception:  # snapcheck: disable=swallowed-exception -- memory accounting never fails the bench
        token = None
    _MEM_SECTION[0] = (name, token, _peak_rss_bytes())


def _mem_section_end() -> None:
    cur = _MEM_SECTION[0]
    if cur is None:
        return
    _MEM_SECTION[0] = None
    name, token, peak0 = cur
    peak1 = _peak_rss_bytes()
    entry: dict = {"peak_rss_bytes": peak1}
    if peak0 is not None and peak1 is not None:
        entry["peak_rss_growth_bytes"] = max(0, peak1 - peak0)
    if token is not None:
        try:
            from torchsnapshot_tpu.telemetry import memwatch

            block = memwatch.window_collect(token)
        except Exception:  # snapcheck: disable=swallowed-exception -- memory accounting never fails the bench
            block = None
        if block:
            entry["memwatch_high_water_bytes"] = block.get(
                "high_water_bytes"
            )
            entry["domains"] = {
                n: d.get("high_water_bytes")
                for n, d in (block.get("domains") or {}).items()
            }
    mem = _RESULTS.setdefault("memory", {"sections": {}})
    mem["sections"][name] = entry
    mem["peak_rss_bytes"] = peak1


def _remaining_s() -> float:
    return _HARD_DEADLINE[0] - time.monotonic()


def _gate(next_work: str, need_s: float) -> None:
    if _remaining_s() < need_s:
        raise _HardDeadline(
            f"{next_work} needs ~{need_s:.0f}s but only "
            f"{max(0.0, _remaining_s()):.0f}s of the hard budget remain"
        )


# Per-section deadline accounting (fastlane satellite): BENCH_r05 lost
# step_stall AND incremental to "skipped: hard deadline" because the
# 176 s consume-dominated restore ate a budget only guarded by one
# blunt constant. Every post-restore section now has its own floor;
# the restore reserves the SUM of all floors up front, and each
# section's gate requires its own floor PLUS the floors of every
# section still behind it — an early overrun can no longer eat a later
# section's floor, and a fixed (fast) restore un-skips everything. The
# verdicts land in the summary's ``section_budget`` block so a reader
# can see where the wall-clock went.
_POST_RESTORE_SECTION_FLOORS = [
    ("incremental", 90.0),
    ("dedup_codec", 75.0),
    ("hot_tier", 75.0),
    ("every_step", 90.0),
    ("wire", 60.0),
    ("repair", 45.0),
    ("read_fanout", 75.0),
    ("fleet", 60.0),
    ("step_stall", 90.0),
]


def _late_sections_reserve_s(after: str = None) -> float:
    """Sum of the post-restore section floors still owed — all of them
    (the restore's up-front reservation), or those strictly BEHIND
    ``after`` (that section's pass-through reserve)."""
    names = [n for n, _ in _POST_RESTORE_SECTION_FLOORS]
    start = names.index(after) + 1 if after is not None else 0
    return sum(f for _, f in _POST_RESTORE_SECTION_FLOORS[start:])


def _section_gate(name: str) -> bool:
    """Whether ``name`` may start: the remaining hard budget must cover
    its own floor plus every later section's floor. Records the verdict
    (and the numbers behind it) into ``section_budget``."""
    own = dict(_POST_RESTORE_SECTION_FLOORS)[name]
    behind = _late_sections_reserve_s(after=name)
    rem = _remaining_s()
    ok = rem >= own + behind
    acct = _RESULTS.setdefault("section_budget", {})
    acct[name] = {
        "floor_s": own,
        "reserve_behind_s": behind,
        "remaining_at_gate_s": round(rem, 1),
        "ran": ok,
    }
    return ok


def _section_done(name: str) -> None:
    acct = (_RESULTS.get("section_budget") or {}).get(name)
    if acct:
        acct["spent_s"] = round(
            acct["remaining_at_gate_s"] - _remaining_s(), 1
        )


def _note_gap(section: str, reason: str) -> None:
    """Record a section the run never measured (deadline/budget): the
    summary's explicit ``gaps`` list, so timeline/bench_compare treat
    it as MISSING data, never as zero (BENCH_r05 silently dropped whole
    sections and the artifact read as if they didn't exist)."""
    gaps = _RESULTS.setdefault("gaps", [])
    if section not in gaps:
        gaps.append(section)
    print(f"[bench] GAP: {section} not measured ({reason})", file=sys.stderr)


def _failed_sections() -> list:
    """Sections that RAN and failed: ``"ok": false`` with no
    ``"skipped"`` reason. Any of them fails the exit code."""
    return sorted(
        name
        for name, doc in _RESULTS.items()
        if isinstance(doc, dict)
        and doc.get("ok") is False
        and "skipped" not in doc
    )


def _summary_doc() -> dict:
    """The one-line summary, built from whatever _RESULTS holds. Keys
    match the clean-run schema exactly; quantities a cut-short run never
    measured are null."""
    r = _RESULTS
    gbps = r.get("take_GBps")
    stall = r.get("async_stall_s")
    elapsed = r.get("take_median_s")
    return {
        "metric": "snapshot_take_GBps",
        "value": round(gbps, 3) if gbps is not None else None,
        "unit": "GB/s",
        "vs_baseline": (
            round(gbps / _REFERENCE_SINGLE_ACCEL_GBPS, 2)
            if gbps is not None
            else None
        ),
        "d2h_ceiling_GBps": r.get("d2h_ceiling_GBps"),
        "take_vs_ceiling": r.get("take_vs_ceiling"),
        "bench_bytes": r.get("bench_bytes"),
        "async_stall_s": stall,
        "async_stall_pct": (
            round(100 * stall / elapsed, 2)
            if stall is not None and elapsed
            else None
        ),
        "restore_GBps": r.get("restore_GBps"),
        "h2d_ceiling_GBps": r.get("h2d_ceiling_GBps"),
        "h2d_probe_spread": r.get("h2d_probe_spread"),
        "restore_vs_ceiling": r.get("restore_vs_ceiling"),
        "restore_bytes": r.get("restore_bytes"),
        "n_take_runs": r.get("n_take_runs", 0),
        "n_restore_attempts": r.get("n_restore_attempts", 0),
        "restore_uncertified": r.get("restore_uncertified", True),
        "restore_read_span_s": r.get("restore_read_span_s", 0),
        "restore_consume_span_s": r.get("restore_consume_span_s", 0),
        "restore_assemble_span_s": r.get("restore_assemble_span_s", 0),
        "h2d_probe_gbps": r.get("h2d_probe_gbps"),
        "restore_consume_profile": r.get("restore_consume_profile"),
        "restore_consume_vs_h2d": r.get("restore_consume_vs_h2d"),
        # Streaming-pipeline sentinel: overlap-engine H2D GB/s over the
        # bracketed ceiling (~1.0 = wire-bound restore).
        "restore_vs_h2d_ceiling": r.get("restore_vs_h2d_ceiling"),
        "section_budget": r.get("section_budget"),
        # telemetry.summarize's dominant-phase call + the doctor's rule
        # hits for the timed restore: the BENCH JSON carries its own
        # diagnosis (BENCH_r05 would have read "consume-dominated"
        # here instead of needing a human to correlate span columns).
        "phase_verdict": r.get("phase_verdict"),
        "doctor_findings": r.get("doctor_findings"),
        "step_stall": r.get("step_stall"),
        "incremental": r.get("incremental"),
        "dedup_codec": r.get("dedup_codec"),
        "hot_tier": r.get("hot_tier"),
        "every_step": r.get("every_step"),
        "wire": r.get("wire"),
        "read_fanout": r.get("read_fanout"),
        "fleet": r.get("fleet"),
        "scaling": r.get("scaling"),
        "sharded_cpu": r.get("sharded_cpu"),
        "memory": r.get("memory"),
        "gaps": r.get("gaps", []),
        "failed_sections": _failed_sections(),
        "degraded": bool(r.get("degraded", True) or r.get("abort")),
        "abort": r.get("abort"),
        "phase_at_exit": _PHASE[0],
        "wall_s": round(time.monotonic() - _BENCH_START[0], 1),
    }


def _emit_summary() -> None:
    """Print the summary JSON exactly once, whoever gets here first."""
    if _EMITTED.is_set():
        return
    _EMITTED.set()
    _mem_section_end()
    print(json.dumps(_summary_doc()))
    sys.stdout.flush()


# ---------------------------------------------------------------- throttle
class _ThrottledStorage:
    """Test-hook decorator simulating a collapsed link: every write/read
    pays payload_bytes/rate of wall-clock on top of the real IO."""

    def __init__(self, inner, gbps: float) -> None:
        self._inner = inner
        self._rate = gbps * 1024**3
        # Serialize IO so the simulated rate is exact (concurrent sleeps
        # would multiply the effective bandwidth by the fan-out).
        self.max_write_concurrency = 1
        self.max_read_concurrency = 1

    async def write(self, io_req) -> None:
        import asyncio

        payload = (
            io_req.data
            if io_req.data is not None
            else io_req.buf.getbuffer()
        )
        await asyncio.sleep(len(payload) / self._rate)
        await self._inner.write(io_req)

    async def read(self, io_req) -> None:
        import asyncio

        from torchsnapshot_tpu.io_types import io_payload

        await self._inner.read(io_req)
        await asyncio.sleep(len(io_payload(io_req)) / self._rate)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _install_throttle() -> None:
    gbps = os.environ.get("TPUSNAPSHOT_BENCH_THROTTLE_GBPS")
    if gbps is None:
        return
    rate = float(gbps)
    import torchsnapshot_tpu.snapshot as _snap_mod
    import torchsnapshot_tpu.storage_plugin as _sp_mod

    orig = _sp_mod.url_to_storage_plugin

    def _throttled(path: str):
        return _ThrottledStorage(orig(path), rate)

    # snapshot.py binds the name at import time — patch both.
    _sp_mod.url_to_storage_plugin = _throttled
    _snap_mod.url_to_storage_plugin = _throttled
    print(
        f"[bench] TEST THROTTLE active: storage capped at {rate} GB/s",
        file=sys.stderr,
    )
_MIN_BENCH_BYTES = 64 * 1024**2
# Opportunistic ceiling (VERDICT r3 #8): when calibration says the link
# can carry it inside the budget, the payload grows toward the
# reference's 18 GB runs instead of idling at the floor.
_MAX_BENCH_BYTES = 8 * 1024**3
# One parameter this large rides the big-object paths the 100 MiB grid
# never touches (VERDICT r3 #3): chunked D2H staging of a single array,
# ONE large storage object on the write side, and the concurrent
# ranged-sub-read reassembly on restore.
_BIG_PARAM_BYTES = 640 * 1024 * 1024


def _phase_verdict(trace_path: str):
    """telemetry.summarize's dominant-phase verdict for one trace —
    embedded in the BENCH JSON so a regression reader sees WHICH phase
    a slow run spent its time in without re-opening the trace."""
    try:
        from torchsnapshot_tpu.telemetry import summarize as _summarize

        summary = _summarize.summarize(
            _summarize.fold_spans(_summarize.load_events(trace_path))
        )
        return summary.get("verdict")
    except Exception:
        return None


def _doctor_findings_for_spans(wall_s: float, spans: dict) -> list:
    """telemetry.doctor findings for the timed restore, from a
    rank-local report synthesized out of the trace's span sums — the
    same shape the flight recorder commits, so the rule table applies
    unchanged. Finding rule ids only (evidence lives in the trace)."""
    try:
        from torchsnapshot_tpu.telemetry import doctor as _doctor

        report = {
            "kind": "restore",
            "ranks": [
                {
                    "rank": 0,
                    "wall_s": wall_s,
                    "phases": {
                        f"{name}_s": round(total, 3)
                        for name, (total, _n) in spans.items()
                    },
                }
            ],
            "totals": {},
        }
        return [f.rule for f in _doctor.diagnose_report(report)]
    except Exception:
        return []


def _restore_trace_breakdown(trace_path: str) -> dict:
    """Aggregate a Chrome trace into {span_name: (total_s, count)}."""
    try:
        with open(trace_path) as f:
            events = json.load(f)["traceEvents"]
    except Exception:
        return {}
    begins, sums, counts = {}, {}, {}
    for e in events:
        if e.get("ph") == "b":
            begins[e["id"]] = e
        elif e.get("ph") == "e" and e.get("id") in begins:
            b = begins.pop(e["id"])
            name = b.get("name", "?")
            sums[name] = sums.get(name, 0.0) + (e["ts"] - b["ts"]) / 1e6
            counts[name] = counts.get(name, 0) + 1
    return {n: (round(sums[n], 2), counts[n]) for n in sums}


def _restore_consume_profile(snap_dir: str) -> dict:
    """The consume_profile block from a just-written restore flight
    report (snapxray): {substeps, consume_s, consume_gbps,
    h2d_probe_gbps?, h2d_fraction?}. {} on any failure — the bench
    headline never depends on observability."""
    try:
        with open(os.path.join(snap_dir, ".report.restore.json")) as f:
            report = json.load(f)
        for summary in report.get("ranks") or []:
            if summary and summary.get("consume_profile"):
                return summary["consume_profile"]
    except Exception:
        pass
    return {}


def _run_cpu_subprocess_bench(script_name: str, timeout_s: float = 600.0) -> dict:
    """Run a benchmarks/ script in a subprocess PINNED to the virtual
    CPU platform (it must never take the chip) and parse its one-line
    JSON. A failure is recorded as {"ok": False, "error": ...} so the
    headline phases still run — and then fails the process's exit code
    (``_failed_sections``). Used for the sharded-path bench (VERDICT
    r3 #3) and the multi-process scaling bench (VERDICT r4 #5)."""
    import subprocess

    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        }
    )
    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks", script_name
    )
    try:
        proc = subprocess.run(
            [sys.executable, script],
            env=env,
            capture_output=True,
            text=True,
            timeout=max(30.0, timeout_s),
        )
        if proc.returncode != 0:
            print(
                f"[bench] {script_name} failed (rc={proc.returncode}): "
                f"{proc.stderr[-500:]}",
                file=sys.stderr,
            )
            return {"ok": False, "error": f"rc={proc.returncode}"}
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:
        print(f"[bench] {script_name} failed: {e!r}", file=sys.stderr)
        return {"ok": False, "error": repr(e)}


def _run_stall_bench(reduced: bool = False) -> dict:
    """Run benchmarks/in_situ_stall.py's loop on the AMBIENT platform
    (the real chip under the driver), IN THIS PROCESS: p50/p95 step-time
    inflation of a live jitted training loop with async_take firing
    mid-loop — the "<5% training step stall" north-star number (VERDICT
    r4 #8), measured against a busy device rather than bench.py's
    idle-device stall. This process already holds the chip, so a child
    that needed it would fail or hang.

    ``reduced=True`` shrinks the loop (fewer steps, smaller model) so a
    tight remaining budget still yields a lower-confidence number
    instead of a skipped section (BENCH_r05). A failure is recorded as
    {"ok": False, "error": ...} and fails the exit code."""
    from benchmarks.in_situ_stall import run_stall

    kwargs = (
        dict(
            n_steps=24, snap_every=8, d_model=256, n_layers=2, seq=256,
            batch=4,
        )
        if reduced
        else {}
    )
    try:
        doc = run_stall(**kwargs)
    except Exception as e:
        traceback.print_exc(file=sys.stderr)
        print(f"[bench] in-situ stall bench failed: {e!r}", file=sys.stderr)
        return {"ok": False, "error": repr(e)}
    doc["ok"] = True
    doc["reduced"] = reduced
    return doc


def _run_incremental_block(
    bench_dir: str, budget_s: float = None, est_gbps: float = None
) -> dict:
    """Incremental-take headline (beyond parity — incremental.py): a
    fingerprinted full take vs a ``base=`` take after mutating 1 of 10
    params. Self-contained bounded payload (100 MiB) so a collapsed
    link cannot let this phase starve the ones after it; the SPEEDUP
    ratio is the certified quantity (both takes cross the same link
    moments apart), not the absolute times.

    Per-section deadline budgeting (BENCH_r05 ate this section with
    ``"skipped: hard deadline"``): when ``budget_s``/``est_gbps`` say
    the full 100 MiB cannot fit, the payload DEGRADES (same 10-param
    shape, smaller params — the dedup-hit structure being certified is
    payload-size independent) down to a 10 MiB floor instead of
    skipping; ``"reduced": true`` marks the result."""
    n_params, param_bytes = 10, 10 << 20
    if budget_s is not None and est_gbps:
        # Two takes + fingerprint/commit overheads must fit the section
        # budget; allot the takes ~25% of it at the estimated link rate.
        movable = est_gbps * 1024**3 * budget_s * 0.25
        param_bytes = int(
            min(10 << 20, max(1 << 20, movable / n_params))
        )
    reduced = param_bytes < 10 << 20
    model = SyntheticModel(
        n_params=n_params, param_bytes=param_bytes, seed=23
    )
    jax.block_until_ready(list(model.params.values()))
    base_dir = f"{bench_dir}/inc-base"
    inc_dir = f"{bench_dir}/inc-next"
    for d in (base_dir, inc_dir):
        shutil.rmtree(d, ignore_errors=True)
    # Warm the fingerprint kernel compile for this param shape outside
    # the timed windows (one jit per shape/dtype, cached).
    from torchsnapshot_tpu.fingerprint import fingerprint_device_async

    jax.block_until_ready(
        fingerprint_device_async(next(iter(model.params.values())))
    )
    begin = time.monotonic()
    base = Snapshot.take(base_dir, {"model": model}, fingerprint=True)
    full_s = time.monotonic() - begin
    # train step analog: one param changes, nine stay frozen
    model.params["param_0"] = model.params["param_0"] + 1.0
    jax.block_until_ready(model.params["param_0"])
    begin = time.monotonic()
    inc = Snapshot.take(inc_dir, {"model": model}, base=base)
    inc_s = time.monotonic() - begin
    manifest = inc.get_manifest()
    hits = sum(
        1
        for e in manifest.values()
        if getattr(e, "base", None) is not None
    )
    ok = hits == n_params - 1
    for d in (base_dir, inc_dir):
        shutil.rmtree(d, ignore_errors=True)
    return {
        "ok": ok,
        "bytes": n_params * param_bytes,
        "changed_params": 1,
        "n_params": n_params,
        "dedup_hits": hits,
        "full_take_s": round(full_s, 3),
        "incremental_take_s": round(inc_s, 3),
        "speedup": round(full_s / max(inc_s, 1e-9), 2),
        "reduced": reduced,
    }


def run_dedup_codec_block(
    bench_dir: str, d2h_gbps: float = None, reduced: bool = False
) -> dict:
    """Content-addressed chunk-store headline (chunkstore.py): an
    unchanged-majority workload taken three times through the chunk
    store, certifying

    (a) a second take of an UNCHANGED model persists < 5% of its
        logical bytes (cross-take dedup);
    (b) a take after dirtying 10% of one large leaf's rows persists
        < 20% of THAT LEAF's logical bytes (sub-leaf dedup — the case
        leaf-granular ``base=`` takes cannot touch);
    (c) lossless codecs restore bit-exact, the opt-in int8 codec
        restores within its documented tolerance
        (codecs.quant_error_bound) and never reaches a non-opted leaf;
    (d) EFFECTIVE take throughput (logical bytes / wall) on the
        unchanged retake exceeds the adjacent D2H probe ceiling — the
        first bench number allowed to beat the hardware bound, because
        unchanged bytes never cross the link at all.

    ``reduced=True`` shrinks the payload for tight budgets / CI smokes
    and skips the ceiling assertion (commit overhead dominates a toy
    payload; the dedup/codec structure being certified is size-
    independent)."""
    import glob as _glob

    from torchsnapshot_tpu import codecs as _codecs

    run = f"{bench_dir}/dedup-run"
    shutil.rmtree(run, ignore_errors=True)
    os.makedirs(run, exist_ok=True)
    n_params, param_bytes, emb_bytes = 8, 32 << 20, 64 << 20
    if reduced:
        n_params, param_bytes, emb_bytes = 4, 4 << 20, 8 << 20
    chunk_bytes = 1 << 20
    saved_env = {
        k: os.environ.get(k)
        for k in ("TPUSNAPSHOT_CHUNK_BYTES", "TPUSNAPSHOT_CHUNK_MIN_BYTES")
    }
    os.environ["TPUSNAPSHOT_CHUNK_BYTES"] = str(chunk_bytes)
    os.environ["TPUSNAPSHOT_CHUNK_MIN_BYTES"] = str(1 << 16)
    lossless = _codecs.best_lossless()
    codec_spec = {"opt/*": "int8", "*": lossless}

    def _store_bytes() -> int:
        return sum(
            os.path.getsize(p)
            for p in _glob.glob(f"{run}/.chunkstore/objects/*/*")
        )

    try:
        model = SyntheticModel(
            n_params=n_params, param_bytes=param_bytes, seed=41
        )
        cols = 1024
        rows = emb_bytes // (cols * 4)
        model.params["embedding"] = jax.random.normal(
            jax.random.key(7), (rows, cols), dtype=jnp.float32
        )
        opt = SyntheticModel(n_params=2, param_bytes=param_bytes, seed=43)
        state = {"model": model, "opt": opt}
        logical = model.total_bytes() + opt.total_bytes()
        jax.block_until_ready(
            list(model.params.values()) + list(opt.params.values())
        )

        # Cold take: every chunk misses; also warms the chunked-
        # fingerprint kernel compiles for these shapes.
        t0 = time.monotonic()
        Snapshot.take(f"{run}/step-1", state, chunks=True, codec=codec_spec)
        cold_s = time.monotonic() - t0
        cold_physical = _store_bytes()
        codec_ratio = cold_physical / logical

        # Unchanged retake, bracketed by an adjacent D2H probe so the
        # effective-throughput ratio pairs the same tenancy moment.
        probe = (
            d2h_gbps
            if d2h_gbps is not None
            else (_probe_d2h_gbps() if not reduced else None)
        )
        t0 = time.monotonic()
        Snapshot.take(f"{run}/step-2", state, chunks=True, codec=codec_spec)
        second_s = time.monotonic() - t0
        second_physical = _store_bytes() - cold_physical
        second_pct = 100.0 * second_physical / logical
        effective_gbps = logical / 1024**3 / max(second_s, 1e-9)
        effective_vs_ceiling = (
            effective_gbps / probe if probe else None
        )

        # Dirty 10% of the embedding's rows (a contiguous trained-row
        # region) — the sub-leaf case leaf dedup cannot touch.
        emb = np.asarray(model.params["embedding"]).copy()
        dirty_rows = max(1, rows // 10)
        emb[:dirty_rows] += 0.125
        model.params["embedding"] = jnp.asarray(emb)
        before3 = _store_bytes()
        t0 = time.monotonic()
        s3 = Snapshot.take(
            f"{run}/step-3", state, chunks=True, codec=codec_spec
        )
        dirty_s = time.monotonic() - t0
        dirty_physical = _store_bytes() - before3
        dirty10_pct = 100.0 * dirty_physical / emb.nbytes
        dirty_take_pct = 100.0 * dirty_physical / logical

        # Codec correctness on the newest take: lossless leaves
        # bit-exact, quantized leaves within the documented bound and
        # NEVER outside the opted-in glob.
        target_model = SyntheticModel(n_params=1, param_bytes=1 << 20)
        target_model.params = {
            k: jnp.zeros_like(v) for k, v in model.params.items()
        }
        target_opt = SyntheticModel(n_params=1, param_bytes=1 << 20)
        target_opt.params = {
            k: jnp.zeros_like(v) for k, v in opt.params.items()
        }
        s3.restore({"model": target_model, "opt": target_opt})
        lossless_exact = all(
            np.array_equal(
                np.asarray(target_model.params[k]),
                np.asarray(model.params[k]),
            )
            for k in model.params
        )
        quant_errs = []
        quant_bounds = []
        for k, v in opt.params.items():
            host = np.asarray(v)
            quant_errs.append(
                float(
                    np.abs(np.asarray(target_opt.params[k]) - host).max()
                )
            )
            quant_bounds.append(_codecs.quant_error_bound(host))
        quant_max_err = max(quant_errs)
        quant_bound = max(quant_bounds)
        quant_ok = all(
            e <= b for e, b in zip(quant_errs, quant_bounds)
        ) and quant_max_err > 0.0
        manifest = s3.get_manifest()
        opt_codecs, other_codecs = set(), set()
        for path, entry in manifest.items():
            recs = getattr(entry, "chunks", None)
            for shard in getattr(entry, "shards", []) or []:
                if shard.array.chunks:
                    (opt_codecs if "/opt/" in f"/{path}" else other_codecs).update(
                        r.get("c") for r in shard.array.chunks
                    )
            if recs:
                (opt_codecs if "/opt/" in f"/{path}" else other_codecs).update(
                    r.get("c") for r in recs
                )
        quant_scoped = "int8" not in other_codecs and (
            opt_codecs == {"int8"}
        )

        # Identity-codec leg: its own tiny run (codecs change chunk
        # KEYS, so mixing codecs inside one run would break the dedup
        # measurement above).
        ident_run = f"{bench_dir}/dedup-ident"
        shutil.rmtree(ident_run, ignore_errors=True)
        os.makedirs(ident_run, exist_ok=True)
        ident = SyntheticModel(n_params=2, param_bytes=1 << 20, seed=47)
        si = Snapshot.take(
            f"{ident_run}/step-1", {"model": ident}, chunks=True, codec=None
        )
        ti = SyntheticModel(n_params=1, param_bytes=1 << 20)
        ti.params = {k: jnp.zeros_like(v) for k, v in ident.params.items()}
        si.restore({"model": ti})
        identity_exact = all(
            np.array_equal(np.asarray(ti.params[k]), np.asarray(v))
            for k, v in ident.params.items()
        )
        shutil.rmtree(ident_run, ignore_errors=True)

        ok = (
            second_pct < 5.0
            and dirty10_pct < 20.0
            and lossless_exact
            and identity_exact
            and quant_ok
            and quant_scoped
            and (
                reduced
                or effective_vs_ceiling is None
                or effective_vs_ceiling > 1.0
            )
        )
        return {
            "ok": bool(ok),
            "reduced": reduced,
            "chunk_bytes": chunk_bytes,
            "codec": lossless,
            "zstd_available": "zstd" in _codecs.available_codecs(),
            "logical_bytes": int(logical),
            "cold_take_s": round(cold_s, 3),
            "cold_physical_bytes": int(cold_physical),
            "codec_ratio": round(codec_ratio, 4),
            "second_take_s": round(second_s, 3),
            "second_take_physical_bytes": int(second_physical),
            "second_take_physical_pct": round(second_pct, 3),
            "effective_gbps": round(effective_gbps, 4),
            "d2h_ceiling_GBps": round(probe, 4) if probe else None,
            "effective_vs_ceiling": (
                round(effective_vs_ceiling, 3)
                if effective_vs_ceiling is not None
                else None
            ),
            "dirty_take_s": round(dirty_s, 3),
            "dirty10_physical_pct": round(dirty10_pct, 3),
            "dirty10_take_physical_pct": round(dirty_take_pct, 3),
            "dirty_rows_fraction": round(dirty_rows / rows, 4),
            "lossless_bit_exact": bool(lossless_exact),
            "identity_bit_exact": bool(identity_exact),
            "quant_max_err": round(quant_max_err, 6),
            "quant_bound": round(quant_bound, 6),
            "quant_within_tolerance": bool(quant_ok),
            "quant_never_outside_opt_in": bool(quant_scoped),
        }
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(run, ignore_errors=True)


def _modeled_remote(gbps: float):
    """Context manager wrapping every resolved storage plugin with the
    token-rate throttle (``_ThrottledStorage``), via the same
    ``set_plugin_wrap_hook`` seam faultline/hottier use (hooks chain):
    the local bench dir stands in for an object store at ``gbps`` of
    read/write bandwidth. Used by the hot-tier sections so the hot-vs-
    durable comparison reflects the production gap (peer RAM vs object
    store) rather than the local page cache — the MODELED rate is
    reported in the section JSON, never passed off as a device number."""
    from contextlib import contextmanager

    @contextmanager
    def _ctx():
        import torchsnapshot_tpu.storage_plugin as _sp_mod

        holder = {}

        def _hook(plugin, url):
            prev = holder["prev"]
            base = prev(plugin, url) if prev is not None else plugin
            return _ThrottledStorage(base, gbps)

        holder["prev"] = _sp_mod.set_plugin_wrap_hook(_hook)
        try:
            yield
        finally:
            _sp_mod.set_plugin_wrap_hook(holder["prev"])

    return _ctx()


def run_hot_tier_block(
    payload_bytes: int = 64 << 20,
    modeled_durable_gbps: float = 0.03,
    n_params: int = 8,
) -> dict:
    """Hot-tier vs durable-tier restore on the SAME snapshot payload
    (hottier/): take with the tier on (ack at RAM, background tier-down),
    then time one restore served from peer RAM against one served from
    the durable tier behind a modeled object-store bandwidth. The
    certified quantity is the ratio ``hot_vs_durable`` (>= 5x is the
    ROADMAP item-5 acceptance bar); ``ok`` only asserts the runs were
    clean (bit-exact, zero hot-tier fallbacks), so a smoke invocation
    with a tiny payload cannot fake the headline. The default modeled
    rate (0.03 GB/s) is GENEROUS to the durable tier: BENCH_r05
    measured the real end-to-end restore at ~0.002 GB/s, 15x slower —
    the reported ratio understates the production gap."""
    from torchsnapshot_tpu import hottier

    import uuid as _uuid

    # memory:// backend: the modeled throttle is the ONLY storage cost,
    # so the ratio measures the tier, not local-disk fsync jitter (the
    # bench dir's disk stalls up to seconds under concurrent writeback).
    root = f"memory://bench-hot-{_uuid.uuid4().hex[:10]}/snap"
    param_bytes = max(1 << 16, payload_bytes // n_params)
    model = SyntheticModel(
        n_params=n_params, param_bytes=param_bytes, seed=31
    )
    jax.block_until_ready(list(model.params.values()))
    reference = {
        k: jax.device_get(v) for k, v in model.params.items()
    }

    def _zero_model():
        target = SyntheticModel(
            n_params=n_params, param_bytes=param_bytes, seed=31
        )
        target.params = {
            k: jnp.zeros_like(v) for k, v in target.params.items()
        }
        return target

    def _timed_restore():
        target = _zero_model()
        begin = time.monotonic()
        Snapshot(root).restore({"model": target})
        jax.block_until_ready(list(target.params.values()))
        elapsed = time.monotonic() - begin
        # Bit-exactness over the WHOLE payload (outside the timed
        # window): certifying on a sampled param would let corruption
        # in the others pass as ok.
        exact = all(
            bool((jax.device_get(target.params[k]) == reference[k]).all())
            for k in reference
        )
        return elapsed, exact

    try:
        with _modeled_remote(modeled_durable_gbps):
            hottier.reset_hot_tier()
            hottier.enable_hot_tier(rank=0, world=2, k=2, drain="background")
            try:
                Snapshot.take(root, {"model": model})
                drained = hottier.wait_drained(timeout_s=600.0)
                # The measured ack->.tierdown window for this take —
                # regression-gated by bench_compare/timeline alongside
                # the ratio (a lag blow-up is a drain-bandwidth
                # regression even when the restore ratio holds).
                durability_lag_s = hottier.durability_lag_s(root)
                hot_s, hot_exact = _timed_restore()
                stats = hottier.runtime().stats_snapshot()
            finally:
                hottier.disable_hot_tier(flush=False)
                hottier.reset_hot_tier()
            # Same snapshot, tier off: every read pays the modeled
            # durable-tier bandwidth.
            durable_s, durable_exact = _timed_restore()
        ratio = durable_s / max(hot_s, 1e-9)
        return {
            "ok": bool(
                drained
                and hot_exact
                and durable_exact
                and stats["fallback_objects"] == 0
            ),
            "bytes": n_params * param_bytes,
            "hot_restore_s": round(hot_s, 3),
            "durable_restore_s": round(durable_s, 3),
            "hot_vs_durable": round(ratio, 2),
            "meets_5x": bool(ratio >= 5.0),
            "durability_lag_s": (
                round(durability_lag_s, 3)
                if durability_lag_s is not None
                else None
            ),
            "modeled_durable_gbps": modeled_durable_gbps,
            "hot_objects": stats["hot_objects"],
            "fallback_objects": stats["fallback_objects"],
        }
    finally:
        import torchsnapshot_tpu.storage_plugin as _sp_mod

        bucket = root.split("://", 1)[1].split("/", 1)[0]
        _sp_mod._MEMORY_STORES.pop(bucket, None)


def run_every_step_block(
    n_steps: int = 6,
    payload_bytes: int = 8 << 20,
    train_step_s: float = 2.5,
    modeled_durable_gbps: float = 0.05,
) -> dict:
    """Every-step checkpointing (the ROADMAP item-5 workload): a train
    loop that async-saves EVERY step, once against the durable tier
    alone (modeled object-store bandwidth) and once with the hot tier
    on, feeding the goodput accountant both times — so the flight
    reports and the manager-base ledger carry the attribution and the
    checkpoint-overhead-above-budget / timeline machinery can certify
    it. ``within_budget`` is the certified verdict: hot-tier overhead
    under ``TPUSNAPSHOT_CKPT_BUDGET_PCT`` (default 5%) at a take
    frequency where the durable tier alone blows the budget."""
    import contextlib

    from torchsnapshot_tpu import CheckpointManager, hottier
    from torchsnapshot_tpu.telemetry import goodput
    from torchsnapshot_tpu.telemetry import ledger as runledger

    budget_pct = float(os.environ.get("TPUSNAPSHOT_CKPT_BUDGET_PCT", 5.0))
    # At every-step cadence with a 2-step retention window, the sweep
    # age guard (default 1h) spares every just-pruned step's young
    # report/progress debris, so prune tombstones accumulate and each
    # step re-drives ALL of them through the modeled-slow storage —
    # measuring tombstone re-driving, not tier overhead. Disable it for
    # the section (both legs identically; restored after).
    prev_age = os.environ.get("TPUSNAPSHOT_SWEEP_MIN_AGE_S")
    os.environ["TPUSNAPSHOT_SWEEP_MIN_AGE_S"] = "0"

    def _loop(tag: str, hot: bool) -> dict:
        import uuid as _uuid

        # memory:// base for the same reason as the hot_tier section:
        # the modeled throttle, not local-disk fsync jitter, must be
        # the storage cost both legs pay.
        base = f"memory://bench-es-{_uuid.uuid4().hex[:8]}/{tag}"
        model = SyntheticModel(
            n_params=4, param_bytes=max(1 << 16, payload_bytes // 4), seed=77
        )
        jax.block_until_ready(list(model.params.values()))
        goodput.reset()
        mgr = CheckpointManager(base, max_to_keep=2)
        tier_ctx = (
            hottier.hot_tier(rank=0, world=2, k=2, drain="background")
            if hot
            else contextlib.nullcontext()
        )
        begin = time.monotonic()
        with _modeled_remote(modeled_durable_gbps):
            with tier_ctx:
                for step in range(n_steps):
                    time.sleep(train_step_s)  # the "train step"
                    goodput.step()
                    mgr.async_save(step, {"model": model}).wait()
                if hot:
                    hottier.wait_drained(timeout_s=600.0)
        wall = time.monotonic() - begin
        gp = goodput.snapshot()
        goodput.reset()
        records, _ = runledger.read_records(base)
        hottier.reset_hot_tier()
        out = {
            "wall_s": round(wall, 3),
            "overhead_pct": gp.get("checkpoint_overhead_pct"),
            "by_mode": gp.get("by_mode"),
            "steps": gp.get("steps"),
            "ledger_records": len(records),
        }
        import torchsnapshot_tpu.storage_plugin as _sp_mod

        _sp_mod._MEMORY_STORES.pop(base.split("://", 1)[1].split("/", 1)[0], None)
        return out

    try:
        durable = _loop("durable", hot=False)
        hot = _loop("hot", hot=True)
        hot_pct = hot.get("overhead_pct")
        durable_pct = durable.get("overhead_pct")
        return {
            "ok": bool(
                hot_pct is not None
                and durable_pct is not None
                and hot["ledger_records"] >= n_steps
                and hot_pct <= durable_pct
            ),
            "n_steps": n_steps,
            "bytes_per_step": payload_bytes,
            "train_step_s": train_step_s,
            "modeled_durable_gbps": modeled_durable_gbps,
            "budget_pct": budget_pct,
            "durable": durable,
            "hot": hot,
            "within_budget": bool(
                hot_pct is not None and hot_pct <= budget_pct
            ),
        }
    finally:
        if prev_age is None:
            os.environ.pop("TPUSNAPSHOT_SWEEP_MIN_AGE_S", None)
        else:
            os.environ["TPUSNAPSHOT_SWEEP_MIN_AGE_S"] = prev_age


def _wire_ops_window(token) -> dict:
    """snapflight: close a wiretap window and shape the per-op
    summaries for the BENCH JSON — p50/p99 latency, deadline margin,
    misses, retries per telemetry key. bench_compare reads this to
    note op-mix and latency shifts between runs (notes, not gates —
    wire latency on shared CI hosts is weather, not regression)."""
    from torchsnapshot_tpu import wiretap

    out = {}
    for key, b in sorted(wiretap.window_collect(token).items()):
        entry = {
            "count": int(b.get("count") or 0),
            "p50_ms": round(float(b.get("p50_s") or 0.0) * 1000, 3),
            "p99_ms": round(float(b.get("p99_s") or 0.0) * 1000, 3),
            "deadline_misses": int(b.get("deadline_misses") or 0),
            "retries": int(b.get("retries") or 0),
        }
        if b.get("margin_p99") is not None:
            entry["margin_p99"] = round(float(b["margin_p99"]), 4)
        out[key] = entry
    return out


def run_wire_block(
    n_steps: int = 4,
    payload_bytes: int = 4 << 20,
    train_step_s: float = 0.4,
) -> dict:
    """Every-step checkpointing with replication crossing REAL process
    boundaries (snapwire): two spawned ``hottier.peer`` subprocesses
    back hosts 1 and 2, k=3 acks require two pushes over actual TCP
    sockets per payload object, and the section certifies the two
    acceptance numbers of ROADMAP item 5: (a) checkpoint overhead stays
    under ``TPUSNAPSHOT_CKPT_BUDGET_PCT`` with acks crossing process
    boundaries, and (b) an unchanged retake's replication
    ``delta_bytes`` < 10% of payload (chunk-granular deltas against the
    peer's acknowledged previous cut)."""
    from torchsnapshot_tpu import CheckpointManager, hottier
    from torchsnapshot_tpu.hottier import transport as wire_transport
    from torchsnapshot_tpu.hottier.peer import spawn_peer
    from torchsnapshot_tpu.telemetry import goodput

    from torchsnapshot_tpu import wiretap

    budget_pct = float(os.environ.get("TPUSNAPSHOT_CKPT_BUDGET_PCT", 5.0))
    prev_age = os.environ.get("TPUSNAPSHOT_SWEEP_MIN_AGE_S")
    os.environ["TPUSNAPSHOT_SWEEP_MIN_AGE_S"] = "0"
    wire_token = wiretap.window_begin()
    procs = []
    try:
        for host in (1, 2):
            proc, _addr, _peer = spawn_peer(
                host_id=host, capacity_bytes=1 << 30
            )
            procs.append(proc)
        import uuid as _uuid

        base = f"memory://bench-wire-{_uuid.uuid4().hex[:8]}/run"
        model = SyntheticModel(
            n_params=4,
            param_bytes=max(1 << 16, payload_bytes // 4),
            seed=99,
        )
        jax.block_until_ready(list(model.params.values()))
        goodput.reset()
        mgr = CheckpointManager(base, max_to_keep=2)
        begin = time.monotonic()
        with hottier.hot_tier(rank=0, world=3, k=3, drain="background"):
            for step in range(n_steps):
                time.sleep(train_step_s)  # the "train step"
                goodput.step()
                mgr.async_save(step, {"model": model}).wait()
            # The unchanged retake: its replication window is the
            # delta-bytes certificate (every chunk matches the peers'
            # acknowledged previous cut, so the pushes are ref frames).
            before = wire_transport.wire_stats_snapshot()
            time.sleep(train_step_s)
            goodput.step()
            mgr.async_save(n_steps, {"model": model}).wait()
            after = wire_transport.wire_stats_snapshot()
            drained = hottier.wait_drained(timeout_s=600.0)
        wall = time.monotonic() - begin
        gp = goodput.snapshot()
        goodput.reset()
        overhead_pct = gp.get("checkpoint_overhead_pct")
        payload_delta = after["payload_bytes"] - before["payload_bytes"]
        wire_delta = after["wire_bytes"] - before["wire_bytes"]
        delta_ratio = (
            round(wire_delta / payload_delta, 4) if payload_delta else None
        )
        totals = {
            k: after[k] - before.get(k, 0)
            for k in (
                "pushes",
                "push_failures",
                "retries",
                "deadline_misses",
            )
        }
        out = {
            "ok": bool(
                overhead_pct is not None
                and delta_ratio is not None
                and delta_ratio < 0.10
                and drained
                and all(p.poll() is None for p in procs)
            ),
            "n_steps": n_steps + 1,
            "bytes_per_step": payload_bytes,
            "train_step_s": train_step_s,
            "budget_pct": budget_pct,
            "wall_s": round(wall, 3),
            "overhead_pct": overhead_pct,
            "within_budget": bool(
                overhead_pct is not None and overhead_pct <= budget_pct
            ),
            "delta_ratio_unchanged": delta_ratio,
            "retake_payload_bytes": payload_delta,
            "retake_wire_bytes": wire_delta,
            "wire": totals,
            "wire_ops": _wire_ops_window(wire_token),
            "peers": len(procs),
        }
        import torchsnapshot_tpu.storage_plugin as _sp_mod

        _sp_mod._MEMORY_STORES.pop(
            base.split("://", 1)[1].split("/", 1)[0], None
        )
        return out
    finally:
        from torchsnapshot_tpu import hottier as _ht

        _ht.disable_hot_tier(flush=False)
        _ht.reset_hot_tier()  # unregisters peers, SIGKILLs spawned procs
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        if prev_age is None:
            os.environ.pop("TPUSNAPSHOT_SWEEP_MIN_AGE_S", None)
        else:
            os.environ["TPUSNAPSHOT_SWEEP_MIN_AGE_S"] = prev_age


def run_repair_block(
    n_steps: int = 2,
    payload_bytes: int = 1 << 20,
    train_step_s: float = 0.2,
    heal_timeout_s: float = 30.0,
) -> dict:
    """Self-healing smoke (snapmend, hottier/repair.py): every-step
    checkpointing over two REAL peer subprocesses with the background
    repair plane attached; one peer is SIGKILLed behind the tier's back
    mid-run and the section measures **time-to-heal** — how long the
    plane takes to classify the loss, respawn the peer one membership
    generation up, and re-replicate every committed undrained object
    back to k live replicas — then certifies a bit-exact restore served
    from a *repaired* (not original) replica and the under-replicated
    gauge back at 0."""
    from torchsnapshot_tpu import CheckpointManager, hottier, telemetry
    from torchsnapshot_tpu.hottier import tier as ht_tier
    from torchsnapshot_tpu.hottier.peer import spawn_peer
    from torchsnapshot_tpu.telemetry import metrics as _mn

    prev_interval = os.environ.get("TPUSNAPSHOT_REPAIR_INTERVAL_S")
    os.environ["TPUSNAPSHOT_REPAIR_INTERVAL_S"] = "0.2"
    procs = []
    try:
        for host in (1, 2):
            proc, _addr, _peer = spawn_peer(
                host_id=host, capacity_bytes=1 << 30
            )
            procs.append(proc)
        import uuid as _uuid

        base = f"memory://bench-mend-{_uuid.uuid4().hex[:8]}/run"
        param_bytes = max(1 << 16, payload_bytes // 2)
        model = SyntheticModel(n_params=2, param_bytes=param_bytes, seed=77)
        jax.block_until_ready(list(model.params.values()))
        reference = {
            k: jax.device_get(v) for k, v in model.params.items()
        }
        mgr = CheckpointManager(base, max_to_keep=2)
        # Manual drain holds the committed objects hot (pending), so
        # the kill really leaves committed undrained bytes below k —
        # the state the repair loop exists for.
        with hottier.hot_tier(
            rank=0, world=4, k=3, drain="manual", repair="background"
        ):
            for step in range(n_steps):
                time.sleep(train_step_s)
                mgr.async_save(step, {"model": model}).wait()
            last_root = f"{base}/step-{n_steps - 1}"
            keys = [
                f"{last_root}/0/model/{name}" for name in model.params
            ]
            assert all(
                len(ht_tier.live_replicas(k)) >= 3 for k in keys
            ), "take did not reach k before the kill"
            procs[0].kill()  # raw SIGKILL behind the tier's back
            procs[0].wait()
            begin = time.monotonic()
            healed = False
            plane = hottier.repair_plane()
            # live_replicas honestly keeps counting the SIGKILLed peer
            # until supervision latches the loss (death is discovered,
            # not assumed), so the heal gate is the plane's own view:
            # loss detected, peer respawned, nothing under-replicated,
            # and the last step's keys back at k.
            while time.monotonic() - begin < heal_timeout_s:
                intro = plane.introspect()
                if (
                    intro["stats"]["peer_restarts"] >= 1
                    and intro["underreplicated_objects"] == 0
                    and all(
                        len(ht_tier.live_replicas(k)) >= 3 for k in keys
                    )
                ):
                    healed = True
                    break
                time.sleep(0.05)
            time_to_heal_s = time.monotonic() - begin
            stats = plane.introspect()["stats"] if plane else {}
            under_bytes = telemetry.gauge(
                _mn.HOT_TIER_UNDERREPLICATED_BYTES
            ).value
            # Restore served from the repaired fleet only: kill the
            # surviving ORIGINAL replica hosts, leaving the respawned
            # peer (whose store holds only repaired bytes).
            ht_tier.kill_host(0)
            ht_tier.kill_host(2)
            target = SyntheticModel(
                n_params=2, param_bytes=param_bytes, seed=77
            )
            target.params = {
                k: jnp.zeros_like(v) for k, v in target.params.items()
            }
            Snapshot(last_root).restore({"model": target})
            jax.block_until_ready(list(target.params.values()))
            exact = all(
                bool(
                    (jax.device_get(target.params[k]) == reference[k]).all()
                )
                for k in reference
            )
            fallbacks = hottier.runtime().stats_snapshot()[
                "fallback_objects"
            ]
            ht_tier.revive_host(0)  # let the drain retire obligations
            hottier.drain_now()
            drained = hottier.wait_drained(timeout_s=600.0)
        out = {
            "ok": bool(
                healed
                and exact
                and drained
                and fallbacks == 0
                and under_bytes == 0.0
                and stats.get("peer_restarts", 0) >= 1
            ),
            "n_steps": n_steps,
            "bytes_per_step": payload_bytes,
            "time_to_heal_s": round(time_to_heal_s, 3),
            "restore_exact_from_repaired": exact,
            "underreplicated_bytes_after": under_bytes,
            "hot_fallbacks": fallbacks,
            "repair": {
                k: stats.get(k, 0)
                for k in (
                    "objects_repaired",
                    "bytes_repaired",
                    "repairs_failed",
                    "escalated_write_throughs",
                    "peer_restarts",
                    "hosts_lost",
                )
            },
        }
        import torchsnapshot_tpu.storage_plugin as _sp_mod

        _sp_mod._MEMORY_STORES.pop(
            base.split("://", 1)[1].split("/", 1)[0], None
        )
        return out
    finally:
        from torchsnapshot_tpu import hottier as _ht

        _ht.disable_hot_tier(flush=False)
        _ht.reset_hot_tier()  # unregisters peers, SIGKILLs spawned procs
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        if prev_interval is None:
            os.environ.pop("TPUSNAPSHOT_REPAIR_INTERVAL_S", None)
        else:
            os.environ["TPUSNAPSHOT_REPAIR_INTERVAL_S"] = prev_interval


class _SharedRateReadThrottle:
    """Plugin decorator modeling ONE object store with a fixed egress
    bandwidth shared by every reader: a global availability pointer
    (threading-locked — readers run on many event loops) serializes the
    modeled transfer slots while the sleeps overlap per caller. Reads
    only (flight-report/ledger writes stay free — the section measures
    read fan-out). Also the section's backend-byte meter."""

    def __init__(self, inner, shared_state: dict) -> None:
        self._inner = inner
        self._shared = shared_state  # {"lock", "avail_at", "rate", "bytes"}

    async def read(self, io_req) -> None:
        import asyncio

        from torchsnapshot_tpu.io_types import io_payload

        await self._inner.read(io_req)
        nbytes = len(io_payload(io_req))
        s = self._shared
        with s["lock"]:
            now = time.monotonic()
            start = max(now, s["avail_at"])
            s["avail_at"] = start + nbytes / s["rate"]
            delay = s["avail_at"] - now
            s["bytes"] += nbytes
        if delay > 0:
            await asyncio.sleep(delay)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def run_read_fanout_block(
    payload_bytes: int = 16 << 20,
    reader_counts=(1, 8, 32),
    modeled_backend_gbps: float = 0.1,
    n_params: int = 8,
) -> dict:
    """Read fan-out through the snapserve read plane vs direct
    (snapserve/, ROADMAP item 3): N concurrent readers restore ONE
    snapshot, once with every reader hitting the backend directly and
    once through an in-process read service, behind a SHARED modeled
    object-store egress bandwidth. The certified quantity is
    backend-byte READ AMPLIFICATION (backend bytes read / snapshot
    payload bytes): direct costs ~N x, the service's manifest memo +
    single-flight + content cache must keep it <= 1.2x at the largest
    N (the ISSUE-9 acceptance bar). Aggregate client GB/s rides along
    (the service serves cached bytes at RAM speed while direct readers
    queue on the shared pipe). Host-only numpy payloads — no device in
    the loop, so the section is tenancy-independent."""
    import asyncio as _asyncio
    import uuid as _uuid

    import numpy as np

    from torchsnapshot_tpu import StateDict, snapserve
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin
    import torchsnapshot_tpu.storage_plugin as _sp_mod

    root = f"memory://bench-fanout-{_uuid.uuid4().hex[:10]}/snap"
    param_bytes = max(1 << 16, payload_bytes // n_params)
    n_elems = param_bytes // 4
    rng = np.random.default_rng(19)
    reference = {
        f"p{i}": rng.standard_normal(n_elems).astype(np.float32)
        for i in range(n_params)
    }
    Snapshot.take(root, {"model": StateDict(**reference)})
    actual_payload = sum(a.nbytes for a in reference.values())

    def _shared_state() -> dict:
        return {
            "lock": threading.Lock(),
            "avail_at": 0.0,
            "rate": modeled_backend_gbps * 1024**3,
            "bytes": 0,
        }

    def _run_group(n_readers: int, make_snapshot) -> dict:
        """N threads restoring concurrently; returns wall/exactness."""
        barrier = threading.Barrier(n_readers)
        spans = [None] * n_readers
        errors: list = []

        def _one(idx: int) -> None:
            try:
                snap = make_snapshot()
                target = {
                    "model": StateDict(
                        **{
                            k: np.zeros_like(v)
                            for k, v in reference.items()
                        }
                    )
                }
                barrier.wait(timeout=60)
                begin = time.monotonic()
                snap.restore(target)
                end = time.monotonic()
                exact = all(
                    bool((target["model"][k] == reference[k]).all())
                    for k in reference
                )
                spans[idx] = (begin, end, exact)
            except Exception as e:  # surfaced via `errors` below
                errors.append(repr(e))

        threads = [
            threading.Thread(target=_one, args=(i,), daemon=True)
            for i in range(n_readers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        if errors or any(s is None for s in spans):
            return {"ok": False, "errors": errors[:3] or ["reader hung"]}
        wall = max(s[1] for s in spans) - min(s[0] for s in spans)
        return {
            "ok": all(s[2] for s in spans),
            "wall_s": round(wall, 3),
            "aggregate_gbps": round(
                n_readers * actual_payload / 1024**3 / max(wall, 1e-9), 4
            ),
        }

    per_n: dict = {}
    try:
        for n_readers in reader_counts:
            # ------------------------------------------------ direct leg
            shared = _shared_state()

            def _hook(plugin, url, shared=shared):
                prev = holder["prev"]
                base = prev(plugin, url) if prev is not None else plugin
                return _SharedRateReadThrottle(base, shared)

            holder = {"prev": _sp_mod.set_plugin_wrap_hook(_hook)}
            try:
                direct = _run_group(n_readers, lambda: Snapshot(root))
            finally:
                _sp_mod.set_plugin_wrap_hook(holder["prev"])
            direct["backend_bytes"] = shared["bytes"]
            direct["amplification"] = round(
                shared["bytes"] / actual_payload, 3
            )

            # ------------------------------------------------ served leg
            # A FRESH server (cold cache) per group so every N measures
            # its own amplification; the modeled throttle lives in the
            # server's backend resolver only — client RPCs must not pay
            # it (that is the disaggregation being measured).
            shared_served = _shared_state()
            service = snapserve.ReadService(
                backend_resolver=lambda url: _SharedRateReadThrottle(
                    url_to_storage_plugin(url), shared_served
                ),
            )
            server = snapserve.start_local_server(service=service)
            fallbacks_before = snapserve.stats_snapshot()[
                "fallback_objects"
            ]
            try:
                served = _run_group(
                    n_readers,
                    lambda: snapserve.RemoteSnapshot(
                        root, addr=server.addr
                    ),
                )
                stats = service.stats()
            finally:
                server.stop()
            served["backend_bytes"] = shared_served["bytes"]
            served["amplification"] = round(
                shared_served["bytes"] / actual_payload, 3
            )
            served["cache_hits"] = stats["cache"]["hits"]
            served["singleflight_collapses"] = stats[
                "singleflight_collapses"
            ]
            # Any fallback means some reads dodged the service — the
            # amplification number would not be measuring the server.
            served["fallbacks"] = (
                snapserve.stats_snapshot()["fallback_objects"]
                - fallbacks_before
            )
            if served["fallbacks"]:
                served["ok"] = False
            per_n[str(n_readers)] = {"direct": direct, "served": served}

        top_n = str(max(reader_counts))
        top = per_n[top_n]
        amplification_served = top["served"].get("amplification")
        meets = bool(
            amplification_served is not None
            and amplification_served <= 1.2
        )
        groups_ok = all(
            g["direct"].get("ok") and g["served"].get("ok")
            for g in per_n.values()
        )
        return {
            "ok": bool(groups_ok and meets),
            "bytes": actual_payload,
            "modeled_backend_gbps": modeled_backend_gbps,
            "readers": per_n,
            "amplification_served": amplification_served,
            "amplification_direct": top["direct"].get("amplification"),
            "served_gbps": top["served"].get("aggregate_gbps"),
            "direct_gbps": top["direct"].get("aggregate_gbps"),
            "meets_1_2x": meets,
        }
    finally:
        _sp_mod._MEMORY_STORES.pop(
            root.split("://", 1)[1].split("/", 1)[0], None
        )


def run_fleet_block(
    payload_bytes: int = 8 << 20,
    n_servers: int = 3,
    n_clients: int = 32,
    modeled_backend_gbps: float = 0.2,
    fairness_quota_bytes: int = 1 << 20,
) -> dict:
    """Snapfleet: N snapserve servers behind one consistent-hash ring,
    32 differently-sharded clients, one shared modeled object-store
    egress. Two certified quantities (ISSUE-17):

    - **Pushdown + sharding**: each client asks the fleet to ``plan``
      its OWN shard slice of one chunk-stored array and fetches only
      the returned chunk records through the ring. Per-client fetched
      bytes must be ≈ its shard fraction (max client ≤ 2x ideal — a
      client re-fetching the whole object is THE pushdown regression),
      and aggregate backend amplification (backend bytes / stored
      payload) ≤ 1.2x: content-keyed routing gives every chunk ONE
      owner, so 32 clients cost ~1x backend work.
    - **Tenant fairness**: against one quota-limited server, a
      saturating tenant must queue behind its OWN quota (deferrals > 0)
      while a small tenant's occasional reads are granted immediately —
      the small tenant's server-side grant-wait p95 stays a small
      fraction of the saturating tenant's
      (``fleet.fairness_p95_ratio``).

    Host-only numpy payloads, in-process servers — tenancy-independent.
    """
    import asyncio as _asyncio
    import uuid as _uuid

    import numpy as np

    from torchsnapshot_tpu import StateDict, snapserve
    from torchsnapshot_tpu.chunkstore import (
        chunk_object_path,
        store_url_for,
    )
    from torchsnapshot_tpu.io_types import IOReq
    from torchsnapshot_tpu.snapserve import pushdown
    from torchsnapshot_tpu.storage_plugin import url_to_storage_plugin
    import torchsnapshot_tpu.storage_plugin as _sp_mod

    from torchsnapshot_tpu import wiretap

    wire_token = wiretap.window_begin()
    root = f"memory://bench-fleet-{_uuid.uuid4().hex[:10]}/snap"
    # Small chunks so every client's shard spans several records; rows
    # divide evenly into n_clients shards so the C-order byte hulls tile
    # the payload exactly.
    rows = n_clients * 8
    cols = max(64, payload_bytes // (4 * rows))
    rng = np.random.default_rng(23)
    reference = rng.standard_normal((rows, cols)).astype(np.float32)
    prev_chunk_bytes = os.environ.get("TPUSNAPSHOT_CHUNK_BYTES")
    os.environ["TPUSNAPSHOT_CHUNK_BYTES"] = str(64 << 10)
    try:
        snap = Snapshot.take(
            root, {"model": StateDict(w=reference)}, chunks=True
        )
    finally:
        if prev_chunk_bytes is None:
            os.environ.pop("TPUSNAPSHOT_CHUNK_BYTES", None)
        else:
            os.environ["TPUSNAPSHOT_CHUNK_BYTES"] = prev_chunk_bytes
    entry = next(
        e
        for e in snap.get_manifest().values()
        if getattr(e, "chunks", None)
    )
    records = entry.chunks
    # Chunk objects live in the run-shared .chunkstore sibling, not
    # under the snapshot root — that store is the backend the fleet
    # fronts here.
    store_root = store_url_for(root)
    record_sizes = [int(r["n"]) for r in records]
    total_stored = sum(record_sizes)
    itemsize = 4

    shared = {
        "lock": threading.Lock(),
        "avail_at": 0.0,
        "rate": modeled_backend_gbps * 1024**3,
        "bytes": 0,
    }
    fleet = snapserve.start_local_fleet(
        n=n_servers,
        service_factory=lambda: snapserve.ReadService(
            backend_resolver=lambda url: _SharedRateReadThrottle(
                url_to_storage_plugin(url), shared
            ),
        ),
    )
    stats_before = snapserve.stats_snapshot()
    client_bytes = [0] * n_clients
    plan_mismatches: list = []
    errors: list = []

    def _one(idx: int) -> None:
        try:
            lo = idx * (rows // n_clients)
            hi = (idx + 1) * (rows // n_clients)
            doc = {
                "shape": [rows, cols],
                "itemsize": itemsize,
                "record_sizes": record_sizes,
                "boxes": [[[lo, hi], [0, cols]]],
            }
            remote = snapserve.plan_remote(
                fleet.addrs[idx % n_servers], doc
            )
            local = pushdown.plan_from_doc(doc)
            if list(remote.get("indices") or []) != list(local["indices"]):
                plan_mismatches.append(
                    {"client": idx, "remote": remote, "local": local}
                )
                return
            plugin = snapserve.SnapServePlugin(
                f"{fleet.addr_spec}/{store_root}"
            )
            try:

                async def _fetch() -> int:
                    got = 0
                    for i in local["indices"]:
                        req = IOReq(path=chunk_object_path(records[i]["k"]))
                        await plugin.read(req)
                        got += len(req.data)
                    return got

                client_bytes[idx] = _asyncio.run(_fetch())
            finally:
                plugin.close()
        except Exception as e:  # surfaced via `errors` below
            errors.append(repr(e))

    threads = [
        threading.Thread(target=_one, args=(i,), daemon=True)
        for i in range(n_clients)
    ]
    begin = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall = time.monotonic() - begin
    fleet.stop()
    stats_after = snapserve.stats_snapshot()
    fallbacks = (
        stats_after["fallback_objects"] - stats_before["fallback_objects"]
    )
    failovers = (
        stats_after["failover_objects"] - stats_before["failover_objects"]
    )

    ideal_fraction = 1.0 / n_clients
    fractions = [b / total_stored for b in client_bytes]
    max_fraction = max(fractions) if fractions else 1.0
    amplification = round(shared["bytes"] / total_stored, 3)
    shard_ok = bool(
        not errors
        and not plan_mismatches
        and all(b > 0 for b in client_bytes)
        and max_fraction <= 2.0 * ideal_fraction
    )
    meets_amp = amplification <= 1.2

    # ------------------------------------------------- tenant fairness
    # One quota-limited server; a saturating tenant hammers it from 8
    # threads while a small tenant issues occasional reads. The quota is
    # SMALLER than one chunk response, so each saturating response is
    # admitted alone (tenant-idle oversize grant) and that tenant's
    # concurrent requests serialize behind their own quota — deferrals
    # with measurable waits — while the small tenant's sequential reads
    # always find their own in-flight at zero and grant immediately.
    # The server's per-tenant grant-wait p95s are the verdict.
    fair: dict = {"ok": False}
    server = snapserve.start_local_server(
        tenant_quota_bytes=fairness_quota_bytes
    )
    try:
        paths = [chunk_object_path(r["k"]) for r in records]
        # The saturating tenant reads a blob LARGER than its quota (and
        # than the socket buffers): each response is admitted alone
        # while its siblings park on the deferred-grant queue — the
        # serialization whose grant waits the p95 measures. The small
        # tenant's sequential chunk reads always find their own
        # in-flight at zero and grant immediately (0-wait samples).
        blob = b"\xa5" * (4 << 20)
        backend = url_to_storage_plugin(store_root)
        try:
            _asyncio.run(
                backend.write(IOReq(path="fairblob", data=blob))
            )
        finally:
            backend.close()

        def _tenant_reads(
            tenant: str, path_list, n_reads: int, out_err: list
        ) -> None:
            plugin = snapserve.SnapServePlugin(
                f"{server.addr}/{store_root}"
            )
            plugin.tenant_override = tenant
            try:

                async def _go() -> None:
                    for j in range(n_reads):
                        req = IOReq(path=path_list[j % len(path_list)])
                        await plugin.read(req)

                _asyncio.run(_go())
            except Exception as e:
                out_err.append(repr(e))
            finally:
                plugin.close()

        fair_errors: list = []
        sat_threads = [
            threading.Thread(
                target=_tenant_reads,
                args=("saturating", ["fairblob"], 6, fair_errors),
                daemon=True,
            )
            for _ in range(8)
        ]
        small_thread = threading.Thread(
            target=_tenant_reads,
            args=("small", paths, 8, fair_errors),
            daemon=True,
        )
        for t in sat_threads:
            t.start()
        time.sleep(0.05)  # let the saturating tenant fill its quota
        small_thread.start()
        for t in sat_threads + [small_thread]:
            t.join(timeout=300)
        tenants = snapserve.fetch_server_stats(server.addr).get(
            "tenants", {}
        )
        sat = tenants.get("saturating") or {}
        small = tenants.get("small") or {}
        sat_p95 = float(sat.get("grant_wait_p95_s") or 0.0)
        small_p95 = float(small.get("grant_wait_p95_s") or 0.0)
        ratio = round(small_p95 / max(sat_p95, 1e-9), 4)
        fair = {
            "ok": bool(
                not fair_errors
                and int(sat.get("deferrals") or 0) > 0
                and (small_p95 <= 0.25 * sat_p95 or small_p95 < 0.005)
            ),
            "quota_bytes": fairness_quota_bytes,
            "saturating": sat,
            "small": small,
            "p95_ratio": ratio,
            "errors": fair_errors[:3],
        }
    finally:
        server.stop()
        _sp_mod._MEMORY_STORES.pop(
            root.split("://", 1)[1].split("/", 1)[0], None
        )

    return {
        "ok": bool(shard_ok and meets_amp and fair["ok"]),
        "bytes": total_stored,
        "n_servers": n_servers,
        "n_clients": n_clients,
        "wall_s": round(wall, 3),
        "records": len(records),
        "per_client_fraction_max": round(max_fraction, 4),
        "per_client_fraction_ideal": round(ideal_fraction, 4),
        "amplification": amplification,
        "meets_1_2x": meets_amp,
        "failovers": failovers,
        "fallbacks": fallbacks,
        "plan_mismatches": plan_mismatches[:3],
        "errors": errors[:3],
        "fairness": fair,
        "fairness_p95_ratio": fair.get("p95_ratio"),
        "wire_ops": _wire_ops_window(wire_token),
    }


def _floor_bytes() -> int:
    return int(os.environ.get("TPUSNAPSHOT_BENCH_FLOOR_BYTES", 1 << 30))


def _restore_floor_bytes() -> int:
    return int(
        os.environ.get(
            "TPUSNAPSHOT_BENCH_RESTORE_FLOOR_BYTES", 512 * 1024**2
        )
    )


def _probe_h2d_gbps() -> float:
    """Measure the current H2D ceiling with the chunked-put transfer the
    restore path itself uses (measured on this platform: chunked sustains
    ~1.4x a single large device_put, so a plain-put probe would understate
    the ceiling), synced by a forced device reduction (device_put returns
    before bytes cross the link here). Best of two, each with a FRESH
    host buffer: re-putting the same array measures a cached/pinned
    staging path 2-3x faster than moving new bytes (measured r3), which
    is not what a restore does. The first run also warms the reduction's
    and concatenate's compiles."""
    import numpy as np

    from torchsnapshot_tpu.ops.transfer import chunked_device_put

    device = jax.devices()[0]
    force = jax.jit(jnp.sum)
    rng = np.random.default_rng(11)
    best = 0.0
    for _ in range(2):
        host = rng.standard_normal(16 * 1024 * 1024, dtype=np.float32)
        begin = time.monotonic()
        arr = chunked_device_put(host, device)
        float(force(arr))
        elapsed = time.monotonic() - begin
        best = max(best, host.nbytes / 1024**3 / elapsed)
        arr.delete()
        del host
    return best


def _probe_d2h_gbps() -> float:
    """Measure the current D2H ceiling with a 64 MB chunked gather.

    Run twice; the first run also warms the slice-kernel compiles. The
    better of the two is the ceiling (interference only subtracts).
    """
    arr = jax.random.normal(jax.random.key(7), (16 * 1024 * 1024,), jnp.float32)
    jax.block_until_ready(arr)
    best = 0.0
    for _ in range(2):
        begin = time.monotonic()
        parallel_device_get(arr)
        elapsed = time.monotonic() - begin
        best = max(best, arr.nbytes / 1024**3 / elapsed)
    return best


def _bench_body(bench_dir: str) -> None:
    bench_start = _BENCH_START[0]
    total_budget_s = _HARD_DEADLINE[0] - bench_start
    env_bytes = os.environ.get("TPUSNAPSHOT_BENCH_BYTES")
    # Device-independent evidence first: the CPU-mesh sharded-path and
    # multi-process scaling benches measure host paths, so a slow device
    # link must not be able to starve them out of the round artifact
    # (r4: the timeout kill lost every number). Budgeted ~5 min of the
    # 20-minute default. They also run BEFORE this process touches the
    # device, as children pinned to the CPU platform.
    # Small budgets (deadline tests, quick manual runs) skip them: their
    # per-phase timeout floors (~60 s of jax import + spawned worlds
    # each) would starve the HEADLINE take/restore evidence instead —
    # the exact inversion of what running-first is for.
    if _remaining_s() >= 300.0:
        _phase("sharded cpu bench")
        _RESULTS["sharded_cpu"] = _run_cpu_subprocess_bench(
            "sharded_cpu_bench.py",
            timeout_s=min(420.0, max(60.0, _remaining_s() * 0.25)),
        )
        print(
            f"[bench] sharded CPU path: {_RESULTS['sharded_cpu']}",
            file=sys.stderr,
        )
        _phase("scaling cpu bench")
        _RESULTS["scaling"] = _run_cpu_subprocess_bench(
            "scaling_cpu_bench.py",
            timeout_s=min(420.0, max(60.0, _remaining_s() * 0.3)),
        )
        print(f"[bench] scaling: {_RESULTS['scaling']}", file=sys.stderr)
    else:
        print(
            f"[bench] skipping CPU sub-benches: "
            f"{_remaining_s():.0f}s budget cannot carry them plus the "
            f"headline phases",
            file=sys.stderr,
        )
        _RESULTS["sharded_cpu"] = {"ok": False, "skipped": "budget"}
        _RESULTS["scaling"] = {"ok": False, "skipped": "budget"}
        _note_gap("sharded_cpu", "budget below the sub-bench floor")
        _note_gap("scaling", "budget below the sub-bench floor")

    _phase("d2h probe")
    d2h_gbps = _probe_d2h_gbps()
    _RESULTS["d2h_ceiling_GBps"] = round(d2h_gbps, 4)
    print(f"[bench] D2H probe ceiling: {d2h_gbps:.4f} GB/s", file=sys.stderr)

    if True:
        _phase("warmup")
        # Warm-up on one representative parameter to exclude one-time
        # costs (imports, thread pools, XLA compiles of the chunked-
        # transfer slice kernels, first D2H) from the measured runs. The
        # warmup take is also the calibration's realistic end-to-end
        # speed sample: the raw probe alone can catch a momentarily
        # quiet link and size a payload the next minute's tenancy cannot
        # move in bounded time (observed: probe 0.0073 GB/s, take one
        # minute later 0.0017 GB/s on the same chip).
        warm_param_bytes = min(
            100 * 1024 * 1024,
            int(env_bytes) if env_bytes is not None else 100 * 1024 * 1024,
        )
        warm = SyntheticModel(n_params=1, param_bytes=warm_param_bytes)
        warm_begin = time.monotonic()
        Snapshot.take(f"{bench_dir}/warmup", {"model": warm})
        warm_elapsed = time.monotonic() - warm_begin
        warm_gbps = warm_param_bytes / 1024**3 / warm_elapsed
        print(
            f"[bench] warmup take: {warm_elapsed:.2f}s "
            f"({warm_gbps:.4f} GB/s end-to-end)",
            file=sys.stderr,
        )
        # Warm the async path too (on-device clone kernel compile).
        Snapshot.async_take(f"{bench_dir}/warmup-async", {"model": warm}).wait()

        degraded = False
        planned_runs = 3
        if env_bytes is not None:
            total_bytes = int(env_bytes)
            degraded = total_bytes < _floor_bytes()
        else:
            # The warmup includes one-time costs, so ~1.3x its speed is a
            # fair steady-state estimate; the probe bounds it above.
            est_gbps = min(d2h_gbps, 1.3 * warm_gbps)
            floor = min(_floor_bytes(), _MAX_BENCH_BYTES)
            floor_gib = floor / 1024**3

            # Refuse to quietly certify a toy payload: while the link
            # estimate cannot carry the floor payload within ~2x the
            # target take window, wait out the tenancy collapse with
            # fresh probes + 100 MiB end-to-end samples (observed
            # collapses recover on minute scales).
            # Anchored HERE, not at bench_start: under a collapsed link
            # the probe + warmups alone can eat minutes, and the recal
            # budget is meant as a wait-for-recovery allowance, not a
            # time-since-process-start cutoff.
            recal_deadline = time.monotonic() + float(
                os.environ.get("TPUSNAPSHOT_BENCH_RECAL_BUDGET_S", 240)
            )
            attempt = 0
            while (
                est_gbps * _TARGET_TAKE_SECONDS * 2 < floor_gib
                and time.monotonic() < recal_deadline
                # Each recal attempt costs ~15s sleep + a probe + a
                # 100 MiB take; never let waiting for tenancy eat the
                # time the measurement itself needs.
                and _remaining_s() > 180
            ):
                attempt += 1
                _phase(f"recalibration {attempt}")
                time.sleep(15)
                probe = _probe_d2h_gbps()
                cal = SyntheticModel(
                    n_params=1, param_bytes=100 * 1024 * 1024, seed=17
                )
                cal_begin = time.monotonic()
                Snapshot.take(f"{bench_dir}/recal-{attempt}", {"model": cal})
                cal_gbps = (100 / 1024) / (time.monotonic() - cal_begin)
                shutil.rmtree(
                    f"{bench_dir}/recal-{attempt}", ignore_errors=True
                )
                est_gbps = min(probe, 1.3 * cal_gbps)
                print(
                    f"[bench] recalibration {attempt}: probe "
                    f"{probe:.4f} GB/s, 100 MiB take {cal_gbps:.4f} GB/s "
                    f"-> estimate {est_gbps:.4f} GB/s",
                    file=sys.stderr,
                )
                d2h_gbps = max(d2h_gbps, probe)

            calibrated = est_gbps * 1024**3 * _TARGET_TAKE_SECONDS
            per_take_floor_s = floor_gib / max(est_gbps, 1e-6)
            restore_reserve_s = min(
                300.0,
                _restore_floor_bytes() / 1024**3 / max(est_gbps, 1e-6)
                + 60.0,
            )
            budget_left_s = (
                total_budget_s
                - (time.monotonic() - bench_start)
                - restore_reserve_s
            )
            if calibrated >= floor:
                total_bytes = int(min(_MAX_BENCH_BYTES, calibrated))
            elif per_take_floor_s * 3 <= budget_left_s:
                # Floor payload takes longer than the target window but
                # three full-size runs still fit: measure at scale.
                total_bytes = floor
            elif per_take_floor_s <= budget_left_s:
                planned_runs = min(
                    3, max(1, int(budget_left_s // per_take_floor_s))
                )
                total_bytes = floor
                print(
                    f"[bench] degraded link: only {planned_runs} "
                    f"floor-size run(s) fit the budget "
                    f"(~{per_take_floor_s:.0f}s each) — fewer runs beat "
                    f"a toy payload",
                    file=sys.stderr,
                )
            else:
                total_bytes = int(
                    min(
                        _MAX_BENCH_BYTES,
                        max(_MIN_BENCH_BYTES, calibrated),
                    )
                )
                degraded = True
                print(
                    f"[bench] CERTIFICATION FLOOR UNREACHABLE: the link "
                    f"(~{est_gbps:.4f} GB/s) cannot move "
                    f"{floor_gib:.1f} GiB within the remaining "
                    f"{budget_left_s:.0f}s budget; falling back to "
                    f"{total_bytes / 1024**3:.2f} GiB and marking the "
                    f"result degraded=true",
                    file=sys.stderr,
                )
        param_bytes = min(100 * 1024 * 1024, total_bytes)
        # A floor-or-better payload includes ONE 640 MiB parameter so the
        # certified run exercises the big-object paths (chunked D2H, one
        # large storage object, split-read restore) alongside the
        # reference-shaped 100 MiB grid. 640 MiB is an exact multiple of
        # the 8/16 MiB transfer chunks: no odd-tail slice kernels.
        use_big = (
            total_bytes >= _floor_bytes()
            and total_bytes >= _BIG_PARAM_BYTES + 2 * param_bytes
        )
        small_target = total_bytes - (_BIG_PARAM_BYTES if use_big else 0)
        # Round the parameter count UP: rounding down would shave a
        # floor-sized payload under the floor (1 GiB is not a multiple of
        # 100 MiB) and falsely mark every at-scale run degraded.
        n_params = max(1, math.ceil(small_target / param_bytes))
        if param_bytes != warm_param_bytes:
            _phase("warmup2")
            # Calibration picked a different parameter shape than the
            # warmup used; warm the new shape's compiles — slice kernels
            # (sync take) AND the on-device clone (async take, whose
            # single stall measurement would otherwise pay first-compile).
            rewarm = SyntheticModel(
                n_params=1, param_bytes=param_bytes, seed=2
            )
            Snapshot.take(f"{bench_dir}/warmup2", {"model": rewarm})
            Snapshot.async_take(
                f"{bench_dir}/warmup2-async", {"model": rewarm}
            ).wait()

        if use_big:
            _phase("warmup-big")
            # Warm the big shape's compiles: D2H slice kernels + the
            # async on-device clone are specialized on the operand shape,
            # and the restore warms the big H2D reassembly so neither
            # timed window pays first-compile.
            bigwarm = SyntheticModel(
                n_params=1, param_bytes=_BIG_PARAM_BYTES, seed=5
            )
            Snapshot.take(f"{bench_dir}/warmup-big", {"model": bigwarm})
            Snapshot.async_take(
                f"{bench_dir}/warmup-big-async", {"model": bigwarm}
            ).wait()
            bigwarm.params = {
                k: jnp.zeros_like(v) for k, v in bigwarm.params.items()
            }
            Snapshot(f"{bench_dir}/warmup-big").restore({"model": bigwarm})
            del bigwarm
            print(
                f"[bench] big-param warmup done "
                f"({time.monotonic() - bench_start:.0f}s elapsed)",
                file=sys.stderr,
            )

        model = SyntheticModel(
            n_params=n_params, param_bytes=param_bytes, dtype=jnp.float32
        )
        if use_big:
            model.params["param_big"] = jax.random.normal(
                jax.random.key(999),
                (_BIG_PARAM_BYTES // 4,),
                dtype=jnp.float32,
            )
        jax.block_until_ready(list(model.params.values()))
        nbytes = model.total_bytes()
        _RESULTS["bench_bytes"] = nbytes
        _RESULTS["degraded"] = degraded
        print(
            f"[bench] payload: {nbytes / 1024**3:.2f} GiB "
            f"({n_params} x {param_bytes >> 20} MiB"
            + (f" + 1 x {_BIG_PARAM_BYTES >> 20} MiB" if use_big else "")
            + ")",
            file=sys.stderr,
        )
        app_state = {"model": model}

        # Flush dirty pages so the measured run isn't throttled by a
        # previous run's writeback (reproducibility; the measured quantity
        # is the wall-clock training is blocked, as in the reference
        # benchmark which also does not fsync).
        try:
            os.sync()
        except Exception:
            pass

        # Median of three runs: the device↔host link is shared, and
        # single-run throughput swings ±30% with interfering traffic. A
        # probe runs ADJACENT to (immediately before) each take so the
        # per-run take/ceiling ratio pairs measurements from the same
        # tenancy moment; the reported take_vs_ceiling is the median of
        # those paired ratios — the estimator least distorted by the
        # minute-scale bandwidth swings.
        times = []
        ratios = []
        probes = [d2h_gbps]
        # Calibration samples tenancy ONCE; if the link collapses
        # mid-measurement (observed: 2.5x inside two minutes), three
        # full runs + restore can blow any external timeout. Stop taking
        # new runs once the cumulative take time passes the soft budget
        # — a 1- or 2-run median is better than a dead benchmark.
        default_take_budget = max(
            200.0,
            total_budget_s - (time.monotonic() - bench_start) - 300.0,
        )
        take_budget_s = float(
            os.environ.get(
                "TPUSNAPSHOT_BENCH_TAKE_BUDGET_S", default_take_budget
            )
        )
        est_first_take_s = (
            nbytes / 1024**3 / max(min(d2h_gbps, 1.3 * warm_gbps), 1e-6)
        )
        for i in range(planned_runs):
            _phase(f"take run {i}")
            # Hard-deadline gate: expected cost of the next run is the
            # slowest observed run (tenancy only gets worse in the cases
            # that matter), or the calibration estimate before any run.
            next_cost = max(times) if times else est_first_take_s
            if times and _remaining_s() < 1.3 * next_cost + 120:
                print(
                    f"[bench] skipping take run {i}: ~{next_cost:.0f}s "
                    f"does not fit the remaining "
                    f"{_remaining_s():.0f}s hard budget",
                    file=sys.stderr,
                )
                break
            if not times:
                _gate("first take run", 1.1 * next_cost + 30)
            shutil.rmtree(f"{bench_dir}/snap", ignore_errors=True)
            try:
                os.sync()
            except Exception:
                pass
            probe_i = _probe_d2h_gbps()
            probes.append(probe_i)
            begin = time.monotonic()
            Snapshot.take(f"{bench_dir}/snap", app_state)
            times.append(time.monotonic() - begin)
            run_gbps = nbytes / 1024**3 / times[-1]
            ratios.append(run_gbps / probe_i)
            # Record incrementally: a supervisor cut mid-run-2 must still
            # report run 1's certified numbers.
            med = sorted(times)[(len(times) - 1) // 2]
            _RESULTS["take_median_s"] = med
            _RESULTS["take_GBps"] = nbytes / 1024**3 / med
            _RESULTS["take_vs_ceiling"] = round(
                sorted(ratios)[(len(ratios) - 1) // 2], 3
            )
            _RESULTS["n_take_runs"] = len(times)
            _RESULTS["d2h_ceiling_GBps"] = round(max(probes), 4)
            print(
                f"[bench] take run {i}: {times[-1]:.2f}s "
                f"({run_gbps:.4f} GB/s; adjacent probe {probe_i:.4f} "
                f"-> ratio {ratios[-1]:.2f})",
                file=sys.stderr,
            )
            if sum(times) > take_budget_s:
                print(
                    f"[bench] take budget exhausted "
                    f"({sum(times):.0f}s > {take_budget_s:.0f}s): "
                    f"tenancy degraded after calibration; using "
                    f"{len(times)} run(s) and shrinking the async/restore "
                    f"payloads",
                    file=sys.stderr,
                )
                break
        # (len-1)//2: with an even count after an early budget break,
        # //2 would select the SLOWER (collapsed-tenancy) run — the
        # opposite of what the truncation is for.
        elapsed = sorted(times)[(len(times) - 1) // 2]
        take_vs_ceiling = sorted(ratios)[(len(ratios) - 1) // 2]
        d2h_gbps = max(probes)

        gbps = nbytes / (1024**3) / elapsed

        # Secondary numbers for humans (stderr; driver parses stdout only).
        # Async stall is measured before restore: restore's H2D transfers
        # keep draining through the device link after it returns, and any
        # subsequent device op (the consistent-cut clone) would wait on
        # that queue — training code would never take a snapshot mid-
        # restore, so that wait is not part of the stall.
        over_budget = sum(times) > take_budget_s
        # The async drain moves its payload over the same link the sync
        # takes just measured; at the measured speed, a full-size drain
        # must plausibly fit what remains of the budget (with the
        # restore still to come) — observed: a mid-run collapse turned a
        # ~100 s expected drain into 20 minutes. The stall metric itself
        # is per-take structure (clone dispatch + one completion wait),
        # not payload-proportional, so shrinking the drain payload does
        # not change what is being certified.
        # Estimate at the SLOWEST observed take, not the median: a
        # collapse on the last run is exactly the case the guard exists
        # for, and the median would average it away. (The drain moves
        # the same payload over the same link, so the slowest take's
        # wall time IS the estimate.)
        expected_drain_s = max(times)
        remaining_s = total_budget_s - (time.monotonic() - bench_start)
        if over_budget or expected_drain_s > 0.4 * remaining_s:
            if not over_budget:
                print(
                    f"[bench] full-size async drain (~{expected_drain_s:.0f}s"
                    f" at measured take speed) does not fit the remaining "
                    f"{remaining_s:.0f}s budget; draining one parameter",
                    file=sys.stderr,
                )
            async_state = {
                "model": SyntheticModel(
                    n_params=1, param_bytes=param_bytes, seed=3
                )
            }
        else:
            async_state = app_state
        _phase("async take")
        async_begin = time.monotonic()
        pending = Snapshot.async_take(f"{bench_dir}/snap-async", async_state)
        async_stall = time.monotonic() - async_begin
        _RESULTS["async_stall_s"] = round(async_stall, 3)
        print(f"[bench] async stall: {async_stall:.3f}s", file=sys.stderr)
        # Bounded waits so a slow drain is visible in the log as it
        # happens, with the drain's current phase, instead of a silent
        # multi-minute gap.
        _phase("async drain")
        while True:
            try:
                pending.wait(timeout_s=min(120.0, max(5.0, _remaining_s())))
                break
            except TimeoutError as e:
                print(
                    f"[bench] async drain still running after "
                    f"{time.monotonic() - async_begin:.0f}s: {e}",
                    file=sys.stderr,
                )
                # The restore needs its own window; abandoning the drain
                # (it finishes in its background thread) and emitting a
                # partial summary beats being killed mid-wait.
                _gate("async drain completion", 120.0)
        print(
            f"[bench] async drain done: {time.monotonic() - async_begin:.2f}s",
            file=sys.stderr,
        )

        # Flush the async snapshot's dirty pages so restore reads don't
        # compete with its writeback.
        try:
            os.sync()
        except Exception:
            pass

        _phase("restore")
        _gate("restore", 60.0)
        # Honest restore timing: device_put returns before bytes cross
        # the device link on this platform, so the timed window must end
        # with a COMPUTE-forced sync — a device-side reduction over the
        # restored arrays cannot produce a result until every byte has
        # landed in HBM (block_until_ready alone is not sufficient here).
        # Default restore payload: the FULL checkpoint when the budget
        # plausibly carries it (the reference's benchmark discipline
        # restores what it saved, and fixed tails — first-read latency,
        # final assembly, the forced sync — amortize over more bytes,
        # so the ratio reflects steady-state throughput); else its own
        # floor; shrunk hard when the takes already overran (degraded
        # tenancy — H2D is the slower direction).
        # Reserve wall-clock for the post-restore sections UP FRONT
        # (BENCH_r04/r05: the restore-certification payload ate the
        # budget and incremental/step_stall ended "skipped: hard
        # deadline" — a degraded round with the dedup headline
        # missing). The reservation is the SUM of the per-section
        # floors (_POST_RESTORE_SECTION_FLOORS), and each section's
        # gate re-checks its floor plus everything behind it — the
        # restore sizes itself against what remains AFTER the
        # reservation, shrinking its own payload rather than starving
        # the sections behind it.
        remaining_for_restore_s = (
            total_budget_s
            - (time.monotonic() - bench_start)
            - _late_sections_reserve_s()
        )
        full_restore_est_s = (
            total_bytes / 1024**3 / max(min(probes), 1e-6) + 30.0
        )
        if over_budget:
            default_restore = min(total_bytes // 4, 100 * 1024 * 1024)
        elif full_restore_est_s < 0.5 * remaining_for_restore_s:
            default_restore = total_bytes
        else:
            default_restore = min(
                total_bytes,
                max(
                    total_bytes // 4,
                    _restore_floor_bytes(),
                    _BIG_PARAM_BYTES if use_big else 0,
                ),
            )
        restore_bytes = int(
            os.environ.get(
                "TPUSNAPSHOT_BENCH_RESTORE_BYTES", default_restore
            )
        )
        # Restore the big parameter FIRST when it fits the restore
        # payload: the split-read reassembly of one large object is
        # exactly the path the certified restore must cover; 100 MiB
        # params fill the rest. (In shrink mode the big param would blow
        # the reduced payload — skip it.)
        parts = [(f"param_{i}", param_bytes) for i in range(n_params)]
        if use_big and restore_bytes >= _BIG_PARAM_BYTES:
            parts = [("param_big", _BIG_PARAM_BYTES)] + parts
        restore_parts = []
        acc = 0
        for name, nb in parts:
            if acc >= restore_bytes and restore_parts:
                break
            restore_parts.append(name)
            acc += nb
        restore_paths = [f"model/{name}" for name in restore_parts]
        param_specs = {
            name: (model.params[name].shape, model.params[name].dtype)
            for name in restore_parts
        }
        # Free the source params' HBM before restoring: at the 8 GiB
        # clamp, source + zeroed templates + streamed transfer chunks
        # would exceed device memory, and the snapshot on disk is the
        # source of truth from here on.
        for v in model.params.values():
            v.delete()

        def _zero_targets():
            out = {
                name: jnp.zeros(shape, dtype)
                for name, (shape, dtype) in param_specs.items()
            }
            jax.block_until_ready(list(out.values()))
            return out

        target = SyntheticModel(n_params=1, param_bytes=1 << 20)
        force_sum = jax.jit(lambda xs: sum(jnp.sum(x) for x in xs))
        # Warm the reduction's compile outside the timed window.
        target.params = _zero_targets()
        float(force_sum([target.params[n] for n in restore_parts]))

        # The restore timing is BRACKETED by H2D probes: the restore
        # window is tens of seconds on a link that swings
        # minute-to-minute, and a single adjacent probe would
        # misattribute a mid-window collapse (or recovery) to the code.
        # If the two probes disagree by more than 2x, the window was
        # unstable — retry once; the attempt with the tighter probe
        # spread is reported, and the spread itself goes in the JSON so
        # a reader can judge the ratio's reliability.
        restored_gib = acc / 1024**3
        from torchsnapshot_tpu import tracing as _tracing

        attempt_counter = [0]

        def _timed_restore():
            attempt_counter[0] += 1
            target.params = _zero_targets()
            trace_path = (
                f"{bench_dir}/restore-trace-{attempt_counter[0]}.json"
            )
            before = _probe_h2d_gbps()
            _tracing.enable(trace_path)
            begin = time.monotonic()
            Snapshot(f"{bench_dir}/snap").restore(
                {"model": target}, paths=restore_paths
            )
            float(force_sum([target.params[n] for n in restore_parts]))
            elapsed = time.monotonic() - begin
            _tracing.flush()
            _tracing.disable()
            after = _probe_h2d_gbps()
            spread = max(before, after) / max(min(before, after), 1e-9)
            # Per-phase breakdown from the trace spans (VERDICT r3 #1:
            # a slow link — read/assemble-dominated — must be
            # distinguishable from a code stall post-hoc). Span seconds
            # are SUMS over concurrent spans, so they can exceed wall.
            spans = _restore_trace_breakdown(trace_path)
            print(
                f"[bench] restore {elapsed:.2f}s; H2D probes "
                f"{before:.4f}/{after:.4f} GB/s (spread {spread:.2f}x); "
                f"phase span-seconds (sum, n): "
                + ", ".join(
                    f"{n}={v[0]}s/{v[1]}" for n, v in sorted(spans.items())
                ),
                file=sys.stderr,
            )
            # Consume sub-phase breakdown (snapxray): the restore's own
            # flight report carries the micro-profiler block; surfacing
            # it in the BENCH JSON is what lets bench_compare name a
            # sub-phase shift across rounds.
            consume_profile = _restore_consume_profile(
                f"{bench_dir}/snap"
            )
            # The CEILING is the better probe (same convention as the
            # D2H probe: interference only subtracts) — a mean could
            # report restore/ceiling above 1.0, which is meaningless.
            return (
                elapsed,
                max(before, after),
                spread,
                spans,
                _phase_verdict(trace_path),
                consume_profile,
            )

        def _ratio(att):
            return (restored_gib / att[0]) / max(att[1], 1e-9)

        # Retry discipline (VERDICT r3 #1): re-time when the probes
        # disagree >2x (unstable window, as before) OR when the
        # restore/ceiling ratio misses 0.5 — a link that dips mid-window
        # and recovers before the trailing probe yields stable probes
        # around a slow restore, which spread-only retry would certify
        # as healthy.
        def _record_restore(attempts_so_far) -> None:
            # Incremental: a supervisor cut mid-retry still reports the
            # best completed attempt.
            el, ceil, spread, spans, verdict, consume_profile = max(
                attempts_so_far, key=_ratio
            )
            r_gbps = restored_gib / el
            r_ratio = r_gbps / max(ceil, 1e-9)
            _RESULTS.update(
                {
                    "restore_GBps": round(r_gbps, 4),
                    "h2d_ceiling_GBps": round(ceil, 4),
                    # The snapxray name for the same bracketed ceiling:
                    # the restore report states consume GB/s as a
                    # fraction of an H2D probe, and the BENCH JSON
                    # carries the probe under the report's field name
                    # so cross-artifact readers need one key.
                    "h2d_probe_gbps": round(ceil, 4),
                    "h2d_probe_spread": round(spread, 2),
                    "restore_vs_ceiling": round(r_ratio, 3),
                    "restore_bytes": int(restored_gib * 1024**3),
                    "n_restore_attempts": len(attempts_so_far),
                    "restore_uncertified": r_ratio < 0.5 or spread > 2.0,
                    "restore_read_span_s": spans.get("read", (0, 0))[0],
                    "restore_consume_span_s": spans.get("consume", (0, 0))[0],
                    "restore_assemble_span_s": spans.get(
                        "assemble", (0, 0)
                    )[0],
                    "phase_verdict": verdict,
                    "doctor_findings": _doctor_findings_for_spans(
                        el, spans
                    ),
                }
            )
            if consume_profile:
                _RESULTS["restore_consume_profile"] = consume_profile
                c_gbps = consume_profile.get("consume_gbps")
                if c_gbps:
                    # Consume against the BRACKETED ceiling (tighter
                    # than the report's one-shot probe): the fraction
                    # ROADMAP item 1's rewrite must push toward 1.0.
                    _RESULTS["restore_consume_vs_h2d"] = round(
                        c_gbps / max(ceil, 1e-9), 4
                    )
                # The streaming pipeline's own sentinel number: the
                # overlap engine's delivered H2D GB/s over the
                # bracketed ceiling. ~1.0 = the wire, not the
                # consumer, is the bottleneck; a slide back toward a
                # consume-serialized restore drops it (gated in
                # bench_compare + timeline as restore_vs_h2d_ceiling).
                o_gbps = consume_profile.get("h2d_overlap_gbps")
                if o_gbps:
                    _RESULTS["restore_vs_h2d_ceiling"] = round(
                        o_gbps / max(ceil, 1e-9), 4
                    )

        attempts = [_timed_restore()]
        _record_restore(attempts)
        while len(attempts) < 3:
            best = max(attempts, key=_ratio)
            unstable = best[2] > 2.0
            slow = _ratio(best) < 0.5
            if not (unstable or slow):
                break
            if over_budget or _remaining_s() < 2.5 * attempts[0][0] + 60:
                break
            print(
                f"[bench] re-timing restore (attempt {len(attempts) + 1}): "
                + (
                    "H2D probes disagree >2x (unstable window)"
                    if unstable
                    else f"restore/ceiling {_ratio(best):.2f} < 0.5 with "
                    f"stable probes — mid-window collapse or code stall"
                ),
                file=sys.stderr,
            )
            attempts.append(_timed_restore())
            _record_restore(attempts)
        (
            restore_elapsed,
            h2d_gbps,
            h2d_spread,
            restore_spans,
            _verdict,
            _consume_profile,
        ) = max(attempts, key=_ratio)
        restore_gbps = restored_gib / restore_elapsed
        restore_vs_ceiling = restore_gbps / max(h2d_gbps, 1e-9)
        # A restore that still misses half its bracketed ceiling (or
        # whose probes never stabilized) is NOT certified, whatever the
        # payload size — the flag the r3 artifact lacked.
        restore_uncertified = restore_vs_ceiling < 0.5 or h2d_spread > 2.0

        # Incremental-take headline (beyond parity): run AFTER the
        # certified take/restore so its bounded 100 MiB payload can
        # never starve them; the two takes bracket the same tenancy
        # moment, so their RATIO is robust to the link's minute-scale
        # swings even when the absolute times are not.
        _phase("incremental take")
        inc_link_gbps = max(min(d2h_gbps, h2d_gbps), 1e-6)
        inc_est_s = 0.1 / inc_link_gbps
        # Reserve headroom for every section behind this one
        # (per-section deadline accounting); the section DEGRADES its
        # payload inside what remains rather than skipping outright
        # (BENCH_r05), and only a budget that cannot carry even the
        # 10 MiB floor records a gap.
        inc_budget_s = _remaining_s() - _late_sections_reserve_s(
            after="incremental"
        )
        if _remaining_s() >= max(
            90.0 + _late_sections_reserve_s(after="incremental"),
            2.2 * inc_est_s + 150.0,
        ):
            inc_budget_s = None  # full budget: no reduction needed
        # Accounting gate (records floors/remaining into
        # section_budget); the RUN decision stays the section's own
        # degrading logic — incremental shrinks its payload inside the
        # pass-through reserve rather than skipping at its full floor.
        _section_gate("incremental")
        if inc_budget_s is not None and (
            inc_budget_s < 30.0
            or inc_link_gbps * 1024**3 * inc_budget_s * 0.25 < 10 << 20
        ):
            _RESULTS["incremental"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _RESULTS["section_budget"]["incremental"]["ran"] = False
            _note_gap(
                "incremental",
                "remaining budget below the 10 MiB reduced floor",
            )
        else:
            _RESULTS["section_budget"]["incremental"]["ran"] = True
            try:
                _RESULTS["incremental"] = _run_incremental_block(
                    bench_dir,
                    budget_s=inc_budget_s,
                    est_gbps=inc_link_gbps if inc_budget_s else None,
                )
            except Exception as e:
                _RESULTS["incremental"] = {"ok": False, "error": repr(e)}
            _section_done("incremental")
        print(
            f"[bench] incremental: {_RESULTS['incremental']}",
            file=sys.stderr,
        )

        # Chunk-store dedup + codec headline (chunkstore.py): the
        # unchanged-majority workload whose effective (logical-bytes)
        # throughput is allowed to BEAT the D2H ceiling — unchanged
        # chunks never cross the link. Bounded payload like the
        # incremental section; degrades to a reduced payload on a tight
        # budget instead of skipping.
        _phase("dedup + codec (chunkstore)")
        if not _section_gate("dedup_codec"):
            _RESULTS["dedup_codec"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap(
                "dedup_codec",
                "remaining budget below the section floor plus the "
                "floors behind it",
            )
        else:
            try:
                _RESULTS["dedup_codec"] = run_dedup_codec_block(
                    bench_dir,
                    d2h_gbps=None,  # probes adjacently inside
                    reduced=_remaining_s() < 240,
                )
            except Exception as e:
                _RESULTS["dedup_codec"] = {"ok": False, "error": repr(e)}
            _section_done("dedup_codec")
        print(
            f"[bench] dedup_codec: {_RESULTS['dedup_codec']}",
            file=sys.stderr,
        )

        # Hot-tier sections (hottier/): CPU + local-fs payloads behind a
        # MODELED object-store bandwidth — tenancy-independent like
        # sharded_cpu, so they run on a fixed small budget. hot_tier
        # certifies the >= 5x hot-vs-durable restore ratio; every_step
        # certifies checkpoint overhead stays under
        # TPUSNAPSHOT_CKPT_BUDGET_PCT at every-step take frequency.
        _phase("hot tier")
        if not _section_gate("hot_tier"):
            _RESULTS["hot_tier"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap("hot_tier", "remaining budget below the section floor")
        else:
            try:
                _RESULTS["hot_tier"] = run_hot_tier_block()
            except Exception as e:
                _RESULTS["hot_tier"] = {"ok": False, "error": repr(e)}
            _section_done("hot_tier")
        print(f"[bench] hot tier: {_RESULTS['hot_tier']}", file=sys.stderr)

        _phase("every-step checkpointing")
        if not _section_gate("every_step"):
            _RESULTS["every_step"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap(
                "every_step", "remaining budget below the section floor"
            )
        else:
            try:
                _RESULTS["every_step"] = run_every_step_block()
            except Exception as e:
                _RESULTS["every_step"] = {"ok": False, "error": repr(e)}
            _section_done("every_step")
        print(
            f"[bench] every_step: {_RESULTS['every_step']}", file=sys.stderr
        )

        # Hot tier over the WIRE (snapwire, ROADMAP item 5): every-step
        # checkpointing with k=3 acks crossing two real peer-process
        # boundaries, plus the unchanged-retake delta-bytes certificate
        # (< 10% of payload on the wire).
        _phase("hot tier over the wire")
        if not _section_gate("wire"):
            _RESULTS["wire"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap("wire", "remaining budget below the section floor")
        else:
            try:
                _RESULTS["wire"] = run_wire_block()
            except Exception as e:
                _RESULTS["wire"] = {"ok": False, "error": repr(e)}
            _section_done("wire")
        print(f"[bench] wire: {_RESULTS['wire']}", file=sys.stderr)

        # Self-healing (snapmend, ROADMAP item 5's churn gap): SIGKILL
        # one of the wire peers mid-run and measure time-to-heal — the
        # background repair plane respawns the peer a generation up
        # and re-replicates committed undrained objects back to k —
        # plus a bit-exact restore from a repaired replica.
        _phase("hot tier self-healing (snapmend)")
        if not _section_gate("repair"):
            _RESULTS["repair"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap("repair", "remaining budget below the section floor")
        else:
            try:
                _RESULTS["repair"] = run_repair_block()
            except Exception as e:
                _RESULTS["repair"] = {"ok": False, "error": repr(e)}
            _section_done("repair")
        print(f"[bench] repair: {_RESULTS['repair']}", file=sys.stderr)

        # Read fan-out through the snapserve read plane (ROADMAP item
        # 3): N in {1, 8, 32} concurrent readers restoring one snapshot
        # through the service vs direct, behind a shared modeled
        # object-store egress. Certifies backend-read amplification
        # <= 1.2x at N=32 (direct pays ~32x). Host-only numpy payloads
        # — tenancy-independent, fixed small budget like hot_tier.
        _phase("read fan-out (snapserve)")
        if not _section_gate("read_fanout"):
            _RESULTS["read_fanout"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap(
                "read_fanout", "remaining budget below the section floor"
            )
        else:
            try:
                _RESULTS["read_fanout"] = run_read_fanout_block()
            except Exception as e:
                _RESULTS["read_fanout"] = {"ok": False, "error": repr(e)}
            _section_done("read_fanout")
        print(
            f"[bench] read_fanout: {_RESULTS['read_fanout']}",
            file=sys.stderr,
        )

        # Snapfleet: N servers behind one consistent-hash ring, 32
        # differently-sharded clients with chunk pushdown, plus the
        # quota-limited tenant-fairness case. Certifies aggregate
        # amplification <= 1.2x and the small tenant's grant-wait p95.
        _phase("read-plane fleet (snapfleet)")
        if not _section_gate("fleet"):
            _RESULTS["fleet"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap(
                "fleet", "remaining budget below the section floor"
            )
        else:
            try:
                _RESULTS["fleet"] = run_fleet_block()
            except Exception as e:
                _RESULTS["fleet"] = {"ok": False, "error": repr(e)}
            _section_done("fleet")
        print(
            f"[bench] fleet: {_RESULTS['fleet']}",
            file=sys.stderr,
        )

        # In-situ step stall on the live device (VERDICT r4 #8): the
        # north star is "<5% TRAINING-STEP stall"; the async_stall above
        # is measured against an idle device. Runs after the restore so
        # nothing else contends for the chip — and in THIS process,
        # which already holds it.
        _phase("in-situ stall")
        if not _section_gate("step_stall"):
            _RESULTS["step_stall"] = {
                "ok": False,
                "skipped": "deadline",
                "error": "skipped: hard deadline",
            }
            _note_gap(
                "step_stall",
                "remaining budget below the reduced-loop floor",
            )
        else:
            # A tight budget runs the REDUCED loop (24 steps, small
            # model) rather than skipping: a lower-confidence stall
            # number beats a silent gap (BENCH_r05).
            _RESULTS["step_stall"] = _run_stall_bench(
                reduced=_remaining_s() < 240,
            )
            _section_done("step_stall")
        print(f"[bench] step stall: {_RESULTS['step_stall']}", file=sys.stderr)

        # Certification verdict: a result is degraded if either headline
        # payload fell below its floor (whatever the reason — collapsed
        # link, exhausted budget, or an explicit small env override), or
        # if the restore measurement itself failed its sanity gate.
        degraded = (
            degraded
            or nbytes < _floor_bytes()
            or restored_gib * 1024**3 < _restore_floor_bytes()
            or restore_uncertified
        )
        if restore_uncertified:
            print(
                f"[bench] RESTORE UNCERTIFIED: restore/ceiling "
                f"{restore_vs_ceiling:.2f} (spread {h2d_spread:.2f}x) "
                f"after {len(attempts)} attempt(s) — see the phase "
                f"breakdown above for the root cause",
                file=sys.stderr,
            )
        if degraded:
            reasons = []
            if nbytes < _floor_bytes():
                reasons.append(
                    f"payload {nbytes / 1024**3:.2f} GiB below floor "
                    f"{_floor_bytes() / 1024**3:.1f} GiB"
                )
            if restored_gib * 1024**3 < _restore_floor_bytes():
                reasons.append(
                    f"restore {restored_gib:.2f} GiB below floor "
                    f"{_restore_floor_bytes() / 1024**3:.1f} GiB"
                )
            if restore_uncertified:
                reasons.append("restore measurement uncertified")
            print(
                f"[bench] DEGRADED RESULT: {'; '.join(reasons)}",
                file=sys.stderr,
            )

        print(
            f"[bench] {nbytes / 1024**3:.2f} GiB, take {elapsed:.2f}s "
            f"({gbps:.3f} GB/s; median paired take/ceiling ratio "
            f"{take_vs_ceiling:.2f}, best probe {d2h_gbps:.3f} GB/s), "
            f"restore[synced] {restored_gib:.2f} GiB in {restore_elapsed:.2f}s "
            f"({restore_gbps:.3f} GB/s), "
            f"async stall {async_stall:.3f}s "
            f"({100 * async_stall / (elapsed + 1e-9):.1f}% of sync take)",
            file=sys.stderr,
        )
        # Final recording + the one JSON line (shared emitter: the same
        # schema the abort/supervisor paths produce, with abort=null).
        _RESULTS["degraded"] = degraded
        _RESULTS["abort"] = None
        _phase("done")
        _emit_summary()


def _cleanup(bench_dir: str, own_dir: bool) -> None:
    if own_dir:
        shutil.rmtree(bench_dir, ignore_errors=True)
        return
    shutil.rmtree(f"{bench_dir}/snap", ignore_errors=True)
    shutil.rmtree(f"{bench_dir}/snap-async", ignore_errors=True)
    shutil.rmtree(f"{bench_dir}/warmup", ignore_errors=True)
    shutil.rmtree(f"{bench_dir}/warmup2", ignore_errors=True)
    shutil.rmtree(f"{bench_dir}/warmup2-async", ignore_errors=True)
    shutil.rmtree(f"{bench_dir}/warmup-async", ignore_errors=True)
    shutil.rmtree(f"{bench_dir}/warmup-big", ignore_errors=True)
    shutil.rmtree(f"{bench_dir}/warmup-big-async", ignore_errors=True)
    import glob as _glob

    for trace in _glob.glob(f"{bench_dir}/restore-trace-*.json"):
        try:
            os.remove(trace)
        except OSError:
            pass


def main() -> int:
    """Run the bench body in a worker thread under a supervisor that
    guarantees the summary JSON is on stdout by the hard deadline,
    whatever the link does (VERDICT r4 #1: the r4 artifact was a
    timeout kill with no parsed JSON). Returns the process exit code:
    non-zero for an abort or a section that ran and failed."""
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(os.path.dirname(os.path.abspath(__file__)))
    _BENCH_START[0] = time.monotonic()
    total_budget_s = float(
        os.environ.get("TPUSNAPSHOT_BENCH_TOTAL_BUDGET_S", 1200)
    )
    _HARD_DEADLINE[0] = _BENCH_START[0] + total_budget_s
    _install_throttle()

    bench_dir = os.environ.get("TPUSNAPSHOT_BENCH_DIR")
    own_dir = bench_dir is None
    if own_dir:
        bench_dir = tempfile.mkdtemp(prefix="tpusnapshot-bench-")

    done = threading.Event()

    def _worker() -> None:
        try:
            _bench_body(bench_dir)
        except _HardDeadline as e:
            print(f"[bench] HARD DEADLINE: {e}", file=sys.stderr)
            _RESULTS["abort"] = f"deadline in phase {_PHASE[0]}: {e}"
            _emit_summary()
        # The body runs on a worker thread, where re-raising would only
        # print: the abort reason is handed to the main thread instead,
        # which prints the summary's exit code (1) as the process's.
        except BaseException as e:  # noqa: BLE001
            traceback.print_exc(file=sys.stderr)
            _RESULTS["abort"] = f"exception in phase {_PHASE[0]}: {e!r}"
            _emit_summary()
        finally:
            done.set()

    worker = threading.Thread(target=_worker, daemon=True, name="bench-body")
    worker.start()
    if not done.wait(timeout=max(1.0, _HARD_DEADLINE[0] - time.monotonic())):
        # The body is stuck inside one blocking call (e.g. a take against
        # a dead link) and cannot run its own abort path. Emit from here
        # and exit hard, non-zero: a flushed, parsed artifact with
        # partial results beats an rc=124 kill with none, but it is not
        # a successful run.
        _RESULTS.setdefault(
            "abort",
            f"hard deadline ({total_budget_s:.0f}s) while stuck in "
            f"phase {_PHASE[0]}",
        )
        print(
            f"[bench] HARD DEADLINE: stuck in phase {_PHASE[0]}; emitting "
            f"partial summary",
            file=sys.stderr,
        )
        _emit_summary()
        sys.stderr.flush()
        _cleanup(bench_dir, own_dir)
        os._exit(1)
    _cleanup(bench_dir, own_dir)
    failed = _failed_sections()
    if failed:
        print(f"[bench] FAILED sections: {failed}", file=sys.stderr)
    return 1 if (failed or _RESULTS.get("abort")) else 0


if __name__ == "__main__":
    sys.exit(main())
