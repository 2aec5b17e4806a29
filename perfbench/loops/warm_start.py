"""Loop kind ``warm_start``: set-up trains a few steps and saves the whole
training state once; the window repeats the start of a new training
stage from that snapshot, with some of its Statefuls restored
(``restore_statefuls``: the weights) and the rest left fresh (the
optimizer at zero).

A cycle: every device array and the manager dropped (``gc.collect``); a
zeroed state made (``fresh_state``: what a new stage's optimizer is); a
new ``CheckpointManager`` on the directory, the latest step resolved and
``restore`` given the named Statefuls alone (``restore``); the stage's
first step, step 0, finished on restored weights and zero moments
(``first_step``). The kill is in-process; what a real start adds on top,
a new process, is this cell's ``setup_s``.

The comparison: every cycle's state, summed on the device before the
step donates it, has to give bit for bit the sums pinned when set-up
saved in every leaf of a restored Stateful, **and the sums of zeros in
every other leaf, which also has to hold no set bit** (a restore that
touched what it was not asked for fails); the progress has to read what it read before unless it was
named; every cycle has to resolve the saved step. The sums are fetched
and compared after the window.

Which leaves of the state a Stateful holds is found by identity (the
job's ``app_state`` hands its Statefuls the state's own arrays), so the
loop names no key of the state.
"""

import gc
import json
import os
import time

import jax
import numpy as np

from torchsnapshot_tpu import CheckpointManager

# What the program's report of a restore says of its selection and of
# the template it let go (``.report.restore.json``; a program without a
# field, as a parent commit, gives None).
_REPORT_FIELDS = (
    "leaves_selected", "bytes_selected", "leaves_in_snapshot",
    "template_released_bytes",
)


def _holds_arrays(stateful) -> bool:
    return any(isinstance(x, jax.Array) for x in jax.tree.leaves(stateful.state_dict()))


def _report_of(base: str, step: int):
    try:
        with open(os.path.join(base, f"step-{step}", ".report.restore.json")) as f:
            rank = json.load(f)["ranks"][0]
    except (OSError, ValueError, KeyError, IndexError):
        return {}
    return {name: rank.get(name) for name in _REPORT_FIELDS}


def _make_sums_and_bits(checksum, restored_leaf):
    """One jitted pass over the state, ``tree -> (the reference's sums,
    bool[n_leaves])``: the sums of every leaf and, of every leaf the
    restore was not given, whether it holds any set bit (False for the
    others, which are held to sums of random weights). The reference's
    sums read a leaf of 2**k equal words whose low bits are zero
    (float32 ones in a matrix) as they read zeros: a leaf the restore
    was not given has to hold no bit at all. In one program, so that a
    leaf is read once where the compiler fuses its reductions."""
    import jax.numpy as jnp

    def holds_bits(x):
        words = jax.lax.bitcast_convert_type(
            x, jnp.dtype(f"uint{8 * x.dtype.itemsize}")
        )
        return jnp.any(words != 0)

    def both(tree):
        bits = [
            jnp.bool_(False) if restored else holds_bits(x)
            for x, restored in zip(jax.tree.leaves(tree), restored_leaf)
        ]
        return checksum(tree), jnp.stack(bits)

    return jax.jit(both)


def _what_is_named(job, named, saved_step):
    """Which leaves of the state the named Statefuls hold (a boolean a
    leaf, in the state's own order), their bytes, and the step the job's
    ``step_of`` has to read after a cycle: the saved one if the Statefuls
    without arrays (the progress) were named, else what it held before."""
    fresh = job.template()
    target = job.app_state(fresh, -1)
    held = {
        id(x) for key in named for x in jax.tree.leaves(target[key].state_dict())
    }
    leaves = jax.tree.leaves(fresh)
    restored = np.array([id(x) in held for x in leaves])
    nbytes = sum(int(x.nbytes) for x, r in zip(leaves, restored) if r)
    plain = [key for key, s in target.items() if not _holds_arrays(s)]
    named_too = bool(plain) and all(key in named for key in plain)
    return restored, nbytes, saved_step if named_too else -1


def run(run) -> None:
    job, traffic = run.job, run.cell.traffic
    save_options = run.cell.config.get("save_options", {})
    named = list(traffic["restore_statefuls"])
    base = os.path.join(run.root, "ckpt")

    run.mark("imports done, job described")
    state = job.init_state()
    step = 0
    for _ in range(int(traffic["warm_steps"])):
        state, _ = job.train_step(state, step)
        step += 1
    run.mark(f"state made, {step} warm steps")
    saved_step = step
    pinned = np.asarray(run.checksum(state))
    CheckpointManager(base).save(
        saved_step, job.app_state(state, saved_step), **save_options
    )
    del state
    run.mark("the earlier stage's whole state is saved")
    run.take_probes()

    restored_leaf, restored_bytes, progress_wanted = _what_is_named(
        job, named, saved_step
    )
    sums_and_bits = _make_sums_and_bits(run.checksum, restored_leaf)
    cycles = []

    def cycle() -> None:
        gc.collect()
        began = time.monotonic()
        with run.note("fresh_state"):
            fresh = job.template()
            target = job.app_state(fresh, -1)
            jax.block_until_ready(fresh)
        made_at = time.monotonic()
        del fresh
        with run.note("restore"):
            got = CheckpointManager(base).restore({key: target[key] for key in named})
        restored_at = time.monotonic()
        state = job.state_of(target)
        # Summed now: the step below donates these buffers.
        sums, holds_bits = sums_and_bits(state)
        with run.note("first_step"):
            state, loss = job.train_step(state, 0)
        ended = time.monotonic()
        cycles.append(
            {
                "resolved": got,
                "progress": job.step_of(target),
                "sums": sums,
                "holds_bits": holds_bits,
                "loss": loss,
                "fresh_state_s": made_at - began,
                "restore_s": restored_at - made_at,
                "first_step_s": ended - restored_at,
                "restored_bytes": restored_bytes,
                "report": _report_of(base, got),
            }
        )

    cycle()  # compiles the zeroed state, the restore path and the step for restored arrays
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
    timed = ("fresh_state_s", "restore_s", "first_step_s", "restored_bytes")
    run.obs["cycles"] = [{k: c[k] for k in timed} for c in cycles]
    run.obs["info"] = {
        "cycles": len(cycles),
        "restore_statefuls": named,
        "leaves_restored": int(restored_leaf.sum()),
        "leaves_of_the_state": len(restored_leaf),
        **{k: [c[k] for c in cycles] for k in timed},
        **{k: [c["report"].get(k) for c in cycles] for k in _REPORT_FIELDS},
        "first_loss": cycles[0]["loss"],
        "first_losses_equal": len({c["loss"] for c in cycles}) == 1,
    }

    # ---- the comparison: no clock from here on
    gc.collect()
    want = np.where(restored_leaf[:, None], pinned, 0).astype(pinned.dtype)
    differing = 0
    wrongly = 0
    for i, c in enumerate(cycles):
        if c["resolved"] != saved_step:
            wrongly += 1
            run.diagnose(
                comparison="step resolved by a fresh manager against step saved",
                cycle=i,
                step=saved_step,
                restore_returned=c["resolved"],
            )
        if c["progress"] != progress_wanted:
            differing += 1
            run.diagnose(
                comparison="the progress after a restore that was "
                + ("given it" if progress_wanted == saved_step else "not given it"),
                cycle=i,
                step=saved_step,
                progress_says=c["progress"],
                wanted=progress_wanted,
            )
        differing += run.compare_sums(
            f"cycle {i}: {named} against sums pinned at the save, every other leaf against zeros",
            saved_step,
            want,
            c["sums"],
        )
        # ... and what the sums of zeros cannot tell from zeros
        sums_say_zero = ~np.asarray(c["sums"]).any(axis=1)
        for leaf in np.flatnonzero(~restored_leaf & sums_say_zero & np.asarray(c["holds_bits"])):
            differing += 1
            run.diagnose(
                comparison=f"cycle {i}: a leaf the restore was not given holds set bits",
                step=saved_step,
                leaf=run.leaf_names[leaf],
            )
    run.compare("leaves_differing", differing, 0)
    run.compare("steps_wrongly_resolved", wrongly, 0)
