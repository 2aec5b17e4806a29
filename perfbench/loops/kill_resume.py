"""Loop kind ``kill_resume``: set-up trains a few steps and saves one;
the window repeats kill -> resume.

A cycle: every device array and the manager dropped (``gc.collect``),
a new ``CheckpointManager`` on the directory, the latest step resolved,
``restore`` into a zeroed template, and the first resumed step finished.
The kill is in-process; what a real kill adds on top, a new process, is
this cell's ``setup_s``.

The comparison: every cycle's restored state, summed on the device
before the resumed step donates it, has to give bit for bit the sums
pinned when set-up saved; every cycle has to resolve the saved step.
The sums are fetched and compared after the window.
"""

import gc
import os
import time

from torchsnapshot_tpu import CheckpointManager, PytreeStateful, StateDict


def run(run) -> None:
    job, traffic = run.job, run.cell.traffic
    save_options = run.cell.config.get("save_options", {})
    base = os.path.join(run.root, "ckpt")

    run.mark("imports done, job described")
    params = job.init_params()
    step = 0
    for _ in range(int(traffic["warm_steps"])):
        params, _ = job.train_step(params, step)
        step += 1
    run.mark(f"parameters made, {step} warm steps")
    saved_step = step
    pinned = run.checksum(params)
    CheckpointManager(base).save(
        saved_step, job.app_state(params, saved_step), **save_options
    )
    params, uninterrupted_loss = job.train_step(params, saved_step)
    del params
    run.mark("the step to resume from is saved")
    run.take_probes()

    cycles = []

    def cycle() -> None:
        gc.collect()
        began = time.monotonic()
        with run.note("restore"):
            target = PytreeStateful({"params": job.zeros_template()})
            progress = StateDict(step=-1)
            got = CheckpointManager(base).restore(
                {"train": target, "progress": progress}
            )
        restored_at = time.monotonic()
        params = target.tree["params"]
        # Summed now: the step below donates these buffers.
        sums = run.checksum(params)
        with run.note("first_step"):
            params, loss = job.train_step(params, got)
        ended = time.monotonic()
        cycles.append(
            {
                "resolved": got,
                "progress": progress["step"],
                "sums": sums,
                "loss": loss,
                "restore_s": restored_at - began,
                "first_step_s": ended - restored_at,
            }
        )

    cycle()  # compiles the restore path and the step for restored arrays
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

    run.metrics["resume_s"] = run.window_s / len(cycles)
    run.obs["cycles"] = [
        {k: c[k] for k in ("restore_s", "first_step_s")} for c in cycles
    ]
    run.obs["info"] = {
        "cycles": len(cycles),
        "restore_s": [c["restore_s"] for c in cycles],
        "first_step_s": [c["first_step_s"] for c in cycles],
        "uninterrupted_loss": uninterrupted_loss,
        "resumed_losses_equal": all(c["loss"] == uninterrupted_loss for c in cycles),
    }

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
        differing += run.compare_sums(
            f"cycle {i}: restored in the window against sums pinned at the save",
            saved_step,
            pinned,
            c["sums"],
        )
    run.compare("leaves_differing", differing, 0)
    run.compare("steps_wrongly_resolved", wrongly, 0)
