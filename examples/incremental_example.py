"""Incremental checkpointing: pay for changed bytes only.

A LoRA-style fine-tune — frozen backbone, small trainable adapter —
checkpointed every "epoch" through CheckpointManager's incremental
mode. The frozen backbone is fingerprinted on device each save and
never re-transferred or re-written; each step's snapshot references the
original writer's objects (chains flatten), restores bit-exactly, and
retention understands the references.

Run (real TPU or CPU):
    python examples/incremental_example.py
"""

import os
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp

from torchsnapshot_tpu import CheckpointManager, Snapshot, StateDict


def payload_files(root: str) -> int:
    n = 0
    for dirpath, _, files in os.walk(root):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), root)
            if rel != ".snapshot_metadata" and not rel.startswith(
                (".completed", ".steps", ".pruning", "refs")
            ):
                n += 1
    return n


def main() -> None:
    rng = np.random.default_rng(0)
    backbone = jnp.asarray(
        rng.standard_normal((1024, 1024), dtype=np.float32)
    )  # 4 MiB, frozen
    adapter_a = jnp.asarray(rng.standard_normal((1024, 8), dtype=np.float32))
    adapter_b = jnp.asarray(rng.standard_normal((8, 1024), dtype=np.float32))

    with tempfile.TemporaryDirectory() as root:
        mgr = CheckpointManager(
            root, max_to_keep=2, incremental=True, full_period=100
        )
        times = []
        for step in range(1, 5):
            # "training": only the adapter changes
            adapter_a = adapter_a + 0.1
            state = {
                "model": StateDict(
                    backbone=backbone, lora_a=adapter_a, lora_b=adapter_b
                )
            }
            begin = time.monotonic()
            mgr.save(step, state)
            times.append(time.monotonic() - begin)
            print(
                f"step {step}: save {times[-1]:.3f}s, "
                f"{payload_files(os.path.join(root, f'step-{step}'))} "
                f"payload object(s) written"
            )

        print(f"steps on disk: {mgr.all_steps()}")
        fresh = {
            "model": StateDict(
                backbone=jnp.zeros_like(backbone),
                lora_a=jnp.zeros_like(adapter_a),
                lora_b=jnp.zeros_like(adapter_b),
            )
        }
        restored_step = mgr.restore(fresh)
        assert restored_step == 4
        assert np.array_equal(
            np.asarray(fresh["model"]["backbone"]), np.asarray(backbone)
        )
        assert np.array_equal(
            np.asarray(fresh["model"]["lora_a"]), np.asarray(adapter_a)
        )
        latest = Snapshot(os.path.join(root, "step-4"))
        assert latest.verify() == {}
        speedup = times[0] / min(times[1:])
        print(
            f"OK: bit-exact restore from incremental chain; "
            f"full {times[0]:.3f}s vs best incremental "
            f"{min(times[1:]):.3f}s ({speedup:.1f}x)"
        )


if __name__ == "__main__":
    main()
