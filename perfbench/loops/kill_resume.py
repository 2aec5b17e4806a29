"""Loop kind ``kill_resume``: set-up trains a few steps and saves one;
the window repeats kill -> resume.

A cycle: every device array and the manager dropped (``gc.collect``),
a new ``CheckpointManager`` on the directory, the latest step resolved,
``restore`` into a zeroed template, and the first resumed step finished.
The kill is in-process; what a real kill adds on top, a new process, is
this cell's ``setup_s``.

With ``restore_layout`` (mesh axes such as ``{"dp": 2, "tp": 2}``) the
restore lands on another layout than the save's: the template is laid
out on it, and the first step is taken by a job built from the same
configuration with ``mesh`` = ``restore_layout`` and the same seed.

The comparison: every cycle's restored state, summed on the device
before the resumed step donates it, has to give bit for bit the sums
pinned when set-up saved; every cycle has to resolve the saved step.
On another layout every copy of every leaf is summed on the devices
that hold it (a leaf replicated over ``dp`` is held twice), and each
copy has to give the pinned sums; the resumed loss is reported and not
compared, for another layout reduces in another order. The sums are
fetched and compared after the window.

What a cycle read is taken from the program's report of the restore
(``.report.restore.json``, moved aside within the cycle and read after
the window): the bytes read into pooled buffers that an earlier read
had filled, into new ones, and into memory the storage plug-in
allocated, which add up to the bytes read. None where the report or a
field is missing.
"""

import contextlib
import gc
import json
import os
import time

from perfbench import manifest, reference
from torchsnapshot_tpu import CheckpointManager

_READ_FIELDS = ("read_pool_hit_bytes", "read_pool_miss_bytes", "read_unpooled_bytes")


def _object_bytes(step_dir: str) -> int:
    """Bytes of the step's objects: every file under it but those whose
    names begin with a dot (metadata, reports)."""
    total = 0
    for directory, _, files in os.walk(step_dir):
        for name in files:
            if not name.startswith("."):
                total += os.path.getsize(os.path.join(directory, name))
    return total


def _read_counts(report: str) -> dict:
    """The three counts of a restore's report, each None where the
    report or the field is missing."""
    try:
        with open(report) as f:
            rank = json.load(f)["ranks"][0]
    except (OSError, ValueError, KeyError, IndexError):
        rank = {}
    counts = {field: rank.get(field) for field in _READ_FIELDS}
    known = None not in counts.values()
    return {"read_bytes": sum(counts.values()) if known else None, **counts}


def run(run) -> None:
    job, traffic = run.job, run.cell.traffic
    layout = traffic.get("restore_layout")
    save_options = run.cell.config.get("save_options", {})
    base = os.path.join(run.root, "ckpt")
    reports = os.path.join(run.root, "restore-reports")
    os.makedirs(reports)

    run.mark("imports done, job described")
    state = job.init_state()
    step = 0
    for _ in range(int(traffic["warm_steps"])):
        state, _ = job.train_step(state, step)
        step += 1
    run.mark(f"state made, {step} warm steps")
    saved_step = step
    pinned = run.checksum(state)
    CheckpointManager(base).save(
        saved_step, job.app_state(state, saved_step), **save_options
    )
    if layout is None:
        state, uninterrupted_loss = job.train_step(state, saved_step)
        resumed, checksum, compare = job, run.checksum, run.compare_sums
    else:
        # The job after the kill: the same configuration and seed, on
        # the layout the restore lands on.
        resumed = manifest.load_module(run.cell.job_path).make_job(
            {**run.cell.config, "mesh": layout}, run.devices, run.seed
        )
        checksum, compare = reference.make_copy_checksum_fn(), run.compare_copies
    del state
    step_dir = os.path.join(base, f"step-{saved_step}")
    stored = _object_bytes(step_dir)
    run.mark(f"the step to resume from is saved, {stored} bytes of objects")
    run.take_probes()

    cycles = []

    def cycle() -> None:
        gc.collect()
        began = time.monotonic()
        with run.note("restore"):
            target = resumed.app_state(job.template(layout), -1)
            got = CheckpointManager(base).restore(target)
        restored_at = time.monotonic()
        state = resumed.state_of(target)
        # Summed now: the step below donates these buffers.
        sums = checksum(state)
        with run.note("first_step"):
            state, loss = resumed.train_step(state, got)
        ended = time.monotonic()
        report = os.path.join(reports, f"{len(cycles)}.json")
        with contextlib.suppress(FileNotFoundError):
            os.replace(os.path.join(step_dir, ".report.restore.json"), report)
        cycles.append(
            {
                "resolved": got,
                "progress": resumed.step_of(target),
                "sums": sums,
                "loss": loss,
                "restore_s": restored_at - began,
                "first_step_s": ended - restored_at,
                "report": report,
            }
        )

    cycle()  # compiles the template, the restore path, the sums and the step for restored arrays
    cycles.clear()
    run.mark("warm-up cycle done")

    profile_at = int(traffic["profile_at_cycle"])
    now = run.open_window()
    trace = run.device_trace
    while run.window_open(now):
        run.attempted += 1
        profiled = trace is not None and len(cycles) == profile_at
        if profiled:
            trace.start(time.monotonic())
        cycle()
        if profiled:
            trace.stop(time.monotonic())
        now = time.monotonic()
    run.close_window()
    run.after_window()
    run.mark(f"window closed: {len(cycles)} cycles")

    for c in cycles:
        c.update(_read_counts(c["report"]))
    run.metrics["resume_s"] = run.window_s / len(cycles)
    timed = ("restore_s", "first_step_s", "read_bytes")
    run.obs["cycles"] = [{k: c[k] for k in timed} for c in cycles]
    run.obs["stored_object_bytes"] = stored
    run.obs["info"] = {
        "cycles": len(cycles),
        "restore_s": [c["restore_s"] for c in cycles],
        "first_step_s": [c["first_step_s"] for c in cycles],
    }
    if layout is None:
        run.obs["info"]["uninterrupted_loss"] = uninterrupted_loss
        run.obs["info"]["resumed_losses_equal"] = all(
            c["loss"] == uninterrupted_loss for c in cycles
        )
    else:
        run.obs["info"].update(
            saved_layout=run.cell.config.get("mesh"),
            restore_layout=layout,
            stored_object_bytes=stored,
            resumed_losses=[c["loss"] for c in cycles],
            **{k: [c[k] for c in cycles] for k in ("read_bytes",) + _READ_FIELDS},
        )

    # ---- the comparison: no clock from here on
    gc.collect()
    differing = 0
    wrongly = 0
    for i, c in enumerate(cycles):
        if c["resolved"] != saved_step or c["progress"] != saved_step:
            wrongly += 1
            run.diagnose(
                comparison="step resolved by a fresh manager against step saved",
                cycle=i,
                step=saved_step,
                restore_returned=c["resolved"],
                progress_says=c["progress"],
            )
        differing += compare(
            f"cycle {i}: restored {f'onto {layout}' if layout else 'in the window'} "
            "against sums pinned at the save",
            saved_step,
            pinned,
            c["sums"],
        )
    run.compare("leaves_differing", differing, 0)
    run.compare("steps_wrongly_resolved", wrongly, 0)
