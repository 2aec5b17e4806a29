"""Loop kind ``save_in_loop``: one training job steps as fast as it can
and calls ``CheckpointManager.async_save`` every ``save_every_steps``
steps, keeping the newest ``keep`` steps.

A closed loop. After every step the job polls the pending handle's
``done()`` and calls ``wait()`` once that is true (the marker and the
pruning happen there); if a save is still pending when the next is due
it waits for it first, and that wait is part of the loop's time. So at
most one save drains while the next state trains: HBM holds the state
at most twice, plus a step's temporaries.

The comparison, made after the window and outside every timed number:
the steps the manager resolves are the newest ``keep`` saved; each is
restored (the newest through latest-step resolution) into a zeroed
template on the traffic's ``check_layout`` and has to give, bit for bit,
the sums pinned when its save was called.
"""

import gc
import os
import time

import jax
import numpy as np

from perfbench.job import key_names
from torchsnapshot_tpu import CheckpointManager, PytreeStateful, StateDict


def _bytes_under(path: str) -> int:
    total = 0
    for directory, _, files in os.walk(path):
        for name in files:
            total += os.path.getsize(os.path.join(directory, name))
    return total


def _leaves_that_raise(base: str, step: int, target) -> list:
    """Which leaves of ``step`` cannot be read back, found by restoring
    one at a time; only a run that already failed pays for this."""
    flat, _ = jax.tree_util.tree_flatten_with_path(target.tree)
    bad = []
    for path, _ in flat:
        name = "train/" + "/".join(key_names(path))
        try:
            CheckpointManager(base).restore({"train": target}, step=step, paths=[name])
        except Exception as e:
            bad.append({"leaf": name, "error": repr(e)[:200]})
    return bad


def _ms(statistic, seconds):
    return float(statistic(seconds)) * 1e3 if seconds else None


def _one_leaf_a_shape(tree):
    seen = {}
    for leaf in jax.tree.leaves(tree):
        seen.setdefault((leaf.shape, leaf.dtype, leaf.sharding), leaf)
    return {f"leaf{i}": leaf for i, leaf in enumerate(seen.values())}


def run(run) -> None:
    job, traffic = run.job, run.cell.traffic
    every = int(traffic["save_every_steps"])
    first = int(traffic["first_save_after_steps"])
    keep = int(traffic["keep"])
    save_options = run.cell.config.get("save_options", {})
    base = os.path.join(run.root, "ckpt")

    run.mark("imports done, job described")
    params = job.init_params()
    step = 0
    for _ in range(int(traffic["warm_steps"])):
        params, _ = job.train_step(params, step)
        step += 1
    run.mark(f"parameters made, {step} warm steps")
    # Warm-up writes next to nothing (the machine's host keeps every
    # block ever written): one leaf of every shape goes through a save
    # into the in-memory backend, which compiles the capture's copies
    # and the drain's per-chunk slices (they depend on the shape alone);
    # the smallest leaf goes through a save into the run's root, which
    # takes the fs backend and the manager through their first use. The
    # sums program compiles with them.
    for warm_base, smallest_only in (
        (f"memory://perfbench-warm-{os.getpid()}/ckpt", False),
        (os.path.join(run.root, "warm"), True),
    ):
        subtree = _one_leaf_a_shape(params)  # of the live, undonated state
        if smallest_only:
            name = min(subtree, key=lambda k: subtree[k].nbytes)
            subtree = {name: subtree[name]}
        warm = CheckpointManager(warm_base).async_save(
            step, job.app_state(subtree, step), **save_options
        )
        params, _ = job.train_step(params, step)
        step += 1
        warm.wait().delete()
    np.asarray(run.checksum(params))
    del warm, subtree
    run.mark("warm-up save drained")
    run.take_probes()

    mgr = CheckpointManager(base, max_to_keep=keep)
    saves = []
    iteration_s = []
    draining = []  # whether a save was called or pending in the iteration
    pending = None
    profile_at = int(traffic["profile_at_save"])
    profile_s = float(traffic["profile_seconds"])

    def finalize() -> None:
        with run.note("wait"):
            pending.wait()
        saves[-1]["durable_s"] = time.monotonic() - saves[-1]["called_at"]

    now = run.open_window()
    run.mark("window opens")
    trace = run.device_trace
    done_steps = 0
    while run.window_open(now):
        began = now
        held = pending is not None
        if done_steps >= first and (done_steps - first) % every == 0:
            if pending is not None:
                finalize()
            if trace is not None and len(saves) == profile_at:
                trace.start(time.monotonic())
                began = time.monotonic()  # the profiler's start is not the loop's
            # Pinned now: the step below donates these buffers.
            pinned = run.checksum(params)
            run.attempted += 1
            called_at = time.monotonic()
            with run.note("async_save"):
                pending = mgr.async_save(
                    step, job.app_state(params, step), **save_options
                )
            saves.append(
                {
                    "step": step,
                    "called_at": called_at,
                    "blocked_s": time.monotonic() - called_at,
                    "pinned": pinned,
                    "durable_s": None,
                }
            )
        with run.note("step"):
            params, _ = job.train_step(params, step)
        step += 1
        done_steps += 1
        if pending is not None and pending.done():
            finalize()
            pending = None
        now = time.monotonic()
        iteration_s.append(now - began)
        draining.append(held or pending is not None)
        # The profiler's stop holds this thread for seconds: not while a
        # save is pending, whose durable time is read by this thread.
        if (
            trace is not None
            and trace.running
            and pending is None
            and now - trace.started_at >= profile_s
        ):
            trace.stop(now)
            now = time.monotonic()
    run.close_window()
    if pending is not None:
        finalize()
        pending = None
    run.after_window()
    run.mark(f"window closed, last save durable: {done_steps} steps, {len(saves)} saves")

    run.metrics["loop_steps_per_s"] = done_steps / run.window_s
    run.obs["saves"] = [
        {k: s[k] for k in ("step", "blocked_s", "durable_s")} for s in saves
    ]
    run.obs["iteration_s"] = iteration_s
    # The free step's median is steady. The iterations under a drain are
    # of two kinds, slowed and not, in about equal numbers, so only
    # their mean is.
    free = [t for t, d in zip(iteration_s, draining) if not d]
    under = [t for t, d in zip(iteration_s, draining) if d]
    run.obs["step_free_ms"] = _ms(np.median, free)
    run.obs["step_in_drain_ms"] = _ms(np.mean, under)
    if free and under and saves:
        run.obs["loop_ms_lost_per_save"] = (
            sum(under) - len(under) * float(np.median(free))
        ) * 1e3 / len(saves)
    newest_dir = os.path.join(base, f"step-{saves[-1]['step']}") if saves else None
    if newest_dir and os.path.isdir(newest_dir):
        run.obs["stored_bytes"] = _bytes_under(newest_dir)
    run.obs["info"] = {
        "steps": done_steps,
        "saves": len(saves),
        "durable_s": [s["durable_s"] for s in saves],
        "blocked_s": [s["blocked_s"] for s in saves],
        "step_free_ms": run.obs["step_free_ms"],
        "step_in_drain_ms": run.obs["step_in_drain_ms"],
        "loop_ms_lost_per_save": run.obs.get("loop_ms_lost_per_save"),
    }

    # ---- the comparison: no clock from here on
    del params, mgr
    gc.collect()
    expected = [s["step"] for s in saves][-keep:]
    resolved = CheckpointManager(base).all_steps()
    wrongly = sorted(set(resolved) ^ set(expected))
    if wrongly:
        run.diagnose(
            comparison="steps the manager resolves against the newest saved",
            resolved=resolved,
            saved_and_kept=expected,
        )
    differing = 0
    progress_wrong = 0
    unrestorable = 0
    pinned_by_step = {s["step"]: s["pinned"] for s in saves}
    for wanted in [s for s in expected if s in resolved]:
        target = PytreeStateful(
            {"params": job.zeros_template(traffic.get("check_layout"))}
        )
        progress = StateDict(step=-1)
        newest = wanted == expected[-1]
        try:
            got = CheckpointManager(base).restore(
                {"train": target, "progress": progress},
                step=None if newest else wanted,
            )
        except Exception as e:  # said aloud, leaf by leaf, and counted
            unrestorable += 1
            run.diagnose(
                comparison="restore after the window raised",
                step=wanted,
                error=repr(e),
                leaves_that_raise=_leaves_that_raise(base, wanted, target),
            )
            continue
        if got != wanted or progress["step"] != wanted:
            progress_wrong += 1
            run.diagnose(
                comparison="step restored against step saved",
                step=wanted,
                restore_returned=got,
                progress_says=progress["step"],
                resolved_latest=newest,
            )
        differing += run.compare_restored(
            "restored after the window against sums pinned at the save call",
            wanted,
            pinned_by_step[wanted],
            target.tree["params"],
        )
        del target
        gc.collect()
    run.mark(f"compared {len(expected)} restored steps")
    run.compare("leaves_differing", differing, 0)
    run.compare("steps_wrongly_resolved", len(wrongly), 0)
    run.compare("steps_wrongly_restored", progress_wrong, 0)
    run.compare("steps_unrestorable", unrestorable, 0)
