"""In-situ async-snapshot stall: step-time inflation inside a real
jitted training loop (VERDICT r2 ask #7).

``bench.py`` measures the async stall against an idle device; the number
a training team quotes is different — how much does taking a snapshot
every K steps inflate the p50/p95 *step time* of a loop that is actually
using the chip? This script runs a jitted transformer SGD loop on the
real device, times every step (blocking on the loss), fires
``Snapshot.async_take`` every K steps mid-loop, and compares the
distribution against a no-snapshot baseline of the same length.

``bench.py`` calls :func:`run_stall` in its own process: a chip belongs
to one process at a time, so a child started by a parent that already
holds it would fail or hang.

Prints one JSON line:
  {"platform": ..., "device_kind": ..., "baseline_p50_s": ...,
   "baseline_p95_s": ..., "snap_p50_s": ..., "snap_p95_s": ...,
   "p50_inflation_pct": ..., "p95_inflation_pct": ...,
   "take_step_overhead_s": ..., "n_steps": ..., "snap_every": ...,
   "param_bytes": ...}

Env knobs: TPUSNAPSHOT_STALL_STEPS (default 60),
TPUSNAPSHOT_STALL_EVERY (default 20), TPUSNAPSHOT_STALL_DMODEL (512),
TPUSNAPSHOT_STALL_LAYERS (4), TPUSNAPSHOT_STALL_SEQ (512),
TPUSNAPSHOT_STALL_BATCH (8), TPUSNAPSHOT_STALL_DIR (fresh tmpdir).
"""

import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from torchsnapshot_tpu import Snapshot  # noqa: E402
from torchsnapshot_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
    init_params,
    sgd_train_step,
)


class _ParamState:
    """Stateful over the training loop's live params pytree."""

    def __init__(self, params):
        self.params = params

    def state_dict(self):
        return {"params": self.params}

    def load_state_dict(self, sd):
        self.params = sd["params"]


def run_stall(
    n_steps: int = 60,
    snap_every: int = 20,
    d_model: int = 512,
    n_layers: int = 4,
    seq: int = 512,
    batch: int = 8,
    bench_dir: Optional[str] = None,
) -> dict:
    """Run the baseline and the snapshotting loop in THIS process on the
    ambient platform and return the result document."""
    config = TransformerConfig(
        vocab_size=1024,
        d_model=d_model,
        n_heads=8,
        n_layers=n_layers,
        d_ff=2048,
        max_seq_len=seq,
    )

    params = init_params(config, jax.random.key(0))
    param_bytes = sum(
        leaf.nbytes for leaf in jax.tree.leaves(params)
    )
    tokens = jax.random.randint(
        jax.random.key(1), (batch, seq), 0, config.vocab_size
    )
    step = jax.jit(
        lambda p, t: sgd_train_step(p, t, config), donate_argnums=(0,)
    )

    own_dir = bench_dir is None
    if own_dir:
        bench_dir = tempfile.mkdtemp(prefix="tpusnapshot-stall-")

    def run_loop(with_snapshots: bool):
        nonlocal params
        times = []
        take_overheads = []
        pendings = []
        state = _ParamState(params)
        for i in range(n_steps):
            begin = time.monotonic()
            if with_snapshots and i > 0 and i % snap_every == 0:
                t0 = time.monotonic()
                state.params = params
                pendings.append(
                    Snapshot.async_take(
                        f"{bench_dir}/step-{i}", {"model": state}
                    )
                )
                take_overheads.append(time.monotonic() - t0)
            params, loss = step(params, tokens)
            # Fetch the loss as a real training loop does: the step
            # time includes the device work, not just its dispatch.
            float(loss)
            times.append(time.monotonic() - begin)
        for p in pendings:
            p.wait()
        return times, take_overheads

    try:
        # Warm-up: compile + let the device settle.
        for _ in range(5):
            params, loss = step(params, tokens)
        jax.block_until_ready(loss)

        base_times, _ = run_loop(with_snapshots=False)
        snap_times, take_overheads = run_loop(with_snapshots=True)

        def p(q, xs):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(q * len(xs)))]

        base_p50, base_p95 = p(0.50, base_times), p(0.95, base_times)
        snap_p50, snap_p95 = p(0.50, snap_times), p(0.95, snap_times)
        # Amortized cost over the whole loop — the number a training team
        # multiplies into their step budget.
        mean_inflation = 100 * (
            sum(snap_times) / max(sum(base_times), 1e-9) - 1
        )
        device = jax.devices()[0]
        result = {
            "platform": device.platform,
            "device_kind": device.device_kind,
            "mean_inflation_pct": round(mean_inflation, 2),
            "baseline_p50_s": round(base_p50, 4),
            "baseline_p95_s": round(base_p95, 4),
            "snap_p50_s": round(snap_p50, 4),
            "snap_p95_s": round(snap_p95, 4),
            "p50_inflation_pct": round(100 * (snap_p50 / base_p50 - 1), 2),
            "p95_inflation_pct": round(100 * (snap_p95 / base_p95 - 1), 2),
            "take_step_overhead_s": round(
                statistics.median(take_overheads), 4
            )
            if take_overheads
            else None,
            "n_steps": n_steps,
            "snap_every": snap_every,
            "param_bytes": param_bytes,
        }
        print(
            f"[stall] baseline p50/p95 {base_p50:.3f}/{base_p95:.3f}s; "
            f"with async_take every {snap_every}: "
            f"{snap_p50:.3f}/{snap_p95:.3f}s; take-call overhead "
            f"{result['take_step_overhead_s']}s; params "
            f"{param_bytes / 1024**2:.1f} MiB",
            file=sys.stderr,
        )
        return result
    finally:
        if own_dir:
            shutil.rmtree(bench_dir, ignore_errors=True)


def main() -> None:
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_REPO_ROOT)
    env = os.environ
    result = run_stall(
        n_steps=int(env.get("TPUSNAPSHOT_STALL_STEPS", 60)),
        snap_every=int(env.get("TPUSNAPSHOT_STALL_EVERY", 20)),
        d_model=int(env.get("TPUSNAPSHOT_STALL_DMODEL", 512)),
        n_layers=int(env.get("TPUSNAPSHOT_STALL_LAYERS", 4)),
        seq=int(env.get("TPUSNAPSHOT_STALL_SEQ", 512)),
        batch=int(env.get("TPUSNAPSHOT_STALL_BATCH", 8)),
        bench_dir=env.get("TPUSNAPSHOT_STALL_DIR"),
    )
    print(json.dumps(result))


if __name__ == "__main__":
    main()
