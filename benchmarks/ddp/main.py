"""Multi-process replicated-snapshot benchmark.

TPU-native analog of reference benchmarks/ddp/main.py:1-70: every process
holds an identical ("DDP-replicated") synthetic model; `Snapshot.take`
with ``replicated=["**"]`` stripes the writes round-robin across
processes, so aggregate throughput scales ~linearly with world size
(reference README table: 0.44 -> 4 GB/s from 1 -> 32 workers). The
baseline is a single process writing everything alone.

Run (single host, N processes):
    python benchmarks/ddp/main.py --nprocs 4 --total-bytes 2147483648

Each worker process coordinates through a FileStore; on a real multi-host
pod, run one process per host with jax.distributed initialized instead and
drop --nprocs. The N workers of one host run on the CPU platform (a chip
belongs to one process at a time, so N processes cannot share it); the
result names the platform the workers reported, so a host-staging number
is never read as a device one.

Aggregate throughput scales with the number of *independent storage
channels*: on a parallel filesystem or object store (the reference used
FSx Lustre; on TPU VMs use ``--url gs://bucket/path``) striping scales
~linearly, while N processes sharing one local disk split a fixed disk
bandwidth and show little speedup. ``--url memory://bench`` removes the
storage bound to show the staging/serialization-path scaling alone.
"""

import argparse
import json
import multiprocessing as mp
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)


def _worker(
    rank, nprocs, store_path, snap_path, total_bytes, out_queue,
    incremental_frac=None,
):
    # snap_path may be any storage URL (fs path, memory://..., gs://...).
    # N workers on one host cannot share a chip: pinned to CPU, and the
    # platform they actually got is part of the result.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(REPO_ROOT)

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.coord import FileStore, NoOpCoordinator, StoreCoordinator
    from torchsnapshot_tpu.models.ddp_synthetic import SyntheticModel

    param_bytes = min(100 * 1024 * 1024, total_bytes)
    n_params = max(1, total_bytes // param_bytes)
    model = SyntheticModel(n_params=n_params, param_bytes=param_bytes, seed=0)
    jax.block_until_ready(list(model.params.values()))

    if nprocs == 1:
        coord = NoOpCoordinator()
    else:
        coord = StoreCoordinator(FileStore(store_path), rank, nprocs, timeout_s=600)

    os.sync()
    # Align processes so startup skew (jax init + model generation) is
    # excluded from the measured window.
    coord.barrier()
    begin = time.monotonic()
    base = Snapshot.take(
        snap_path,
        {"model": model},
        coord=coord,
        replicated=["**"],
        fingerprint=bool(incremental_frac is not None),
    )
    elapsed = time.monotonic() - begin

    inc_elapsed = None
    if incremental_frac is not None:
        # A "training step" touches ceil(frac * n_params) params; the
        # rest dedup against the base — the checkpoint-every-N-steps
        # cost the reference benchmark cannot express.
        n_changed = max(1, int(round(incremental_frac * len(model.params))))
        for name in sorted(model.params)[:n_changed]:
            model.params[name] = model.params[name] + jnp.float32(1)
        jax.block_until_ready(list(model.params.values()))
        coord.barrier()
        inc_begin = time.monotonic()
        Snapshot.take(
            f"{snap_path}-inc",
            {"model": model},
            coord=coord,
            replicated=["**"],
            base=base,
        )
        inc_elapsed = time.monotonic() - inc_begin

    # Per-rank bytes actually written — the striping evidence. For
    # memory:// each process has its own private "bucket", so its store
    # holds exactly this rank's writes (the payload objects plus, on
    # rank 0, the metadata document).
    rank_bytes = None
    if snap_path.startswith("memory://"):
        from torchsnapshot_tpu.storage_plugin import _MEMORY_STORES

        # memory:// is hierarchical (bucket + key prefix): the store is
        # keyed by the first path segment and this snapshot's objects
        # carry the remainder as a key prefix.
        root = snap_path[len("memory://") :]
        bucket, _, prefix = root.partition("/")
        prefix = f"{prefix.rstrip('/')}/" if prefix else ""
        store = _MEMORY_STORES.get(bucket, {})
        rank_bytes = sum(
            len(v)
            for k, v in store.items()
            if k.startswith(prefix)
            and not k[len(prefix) :].startswith(".snapshot")
        )
    out_queue.put(
        (
            rank,
            elapsed,
            model.total_bytes(),
            rank_bytes,
            inc_elapsed,
            jax.default_backend(),
        )
    )


def run(
    nprocs: int,
    total_bytes: int,
    base_dir: str,
    url: Optional[str] = None,
    incremental_frac: Optional[float] = None,
) -> dict:
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    store = os.path.join(base_dir, f"store-{nprocs}")
    snap = (
        f"{url.rstrip('/')}/snap-{nprocs}"
        if url
        else os.path.join(base_dir, f"snap-{nprocs}")
    )
    procs = [
        ctx.Process(
            target=_worker,
            args=(r, nprocs, store, snap, total_bytes, q, incremental_frac),
        )
        for r in range(nprocs)
    ]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=1200)
    for p in procs:
        if p.exitcode != 0:
            raise RuntimeError(f"worker failed with exit code {p.exitcode}")
    results = [q.get(timeout=10) for _ in range(nprocs)]
    elapsed = next(e for r, e, *_ in results if r == 0)
    nbytes = results[0][2]
    per_rank = {r: b for r, _, _, b, *_ in results if b is not None}
    out = {
        "nprocs": nprocs,
        "platform": "+".join(sorted({res[5] for res in results})),
        "seconds": round(elapsed, 2),
        "GBps": round(nbytes / 1024**3 / elapsed, 3),
    }
    inc_times = [
        i for r, _, _, _, i, _ in results if r == 0 and i is not None
    ]
    if inc_times:
        out["incremental_seconds"] = round(inc_times[0], 2)
        out["incremental_speedup"] = round(
            elapsed / max(inc_times[0], 1e-9), 2
        )
    if per_rank:
        out["per_rank_written_MB"] = {
            r: round(b / 1024**2, 1) for r, b in sorted(per_rank.items())
        }
        # The striping claim, asserted: replicated values stripe round-
        # robin, so the busiest rank writes ~1/N of the total (within one
        # 100 MB parameter of granularity).
        expect = nbytes / nprocs
        slack = 100 * 1024 * 1024
        busiest = max(per_rank.values())
        if busiest > expect + slack:
            raise AssertionError(
                f"striping failed: busiest rank wrote {busiest} bytes, "
                f"expected ≈{expect:.0f} (±{slack})"
            )
    return out


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--nprocs", type=int, default=4)
    parser.add_argument("--total-bytes", type=int, default=2 * 1024**3)
    parser.add_argument("--work-dir", default=None)
    parser.add_argument(
        "--url",
        default=None,
        help="storage URL prefix (e.g. gs://bucket/bench, memory://bench); "
        "default: a directory under --work-dir",
    )
    parser.add_argument(
        "--incremental-frac",
        type=float,
        default=None,
        help="also measure an INCREMENTAL take after mutating this "
        "fraction of params (0.1 = a step that touches 10%% of the "
        "model); reports the per-run speedup of take(base=prev) over "
        "the full take",
    )
    args = parser.parse_args()

    base_dir = args.work_dir or tempfile.mkdtemp(prefix="tpusnapshot-ddp-")
    ns = sorted({1, 2, args.nprocs} if args.nprocs >= 2 else {1})
    try:
        results = []
        for n in ns:
            res = run(
                n,
                args.total_bytes,
                base_dir,
                url=args.url,
                incremental_frac=args.incremental_frac,
            )
            results.append(res)
            print(json.dumps(res), file=sys.stderr)
        speedup = results[-1]["GBps"] / max(results[0]["GBps"], 1e-9)
        print(
            json.dumps(
                {
                    "metric": "ddp_replicated_snapshot_speedup",
                    "value": round(speedup, 2),
                    "unit": f"x ({args.nprocs} procs vs 1)",
                    "platform": results[-1]["platform"],
                    "runs": results,
                }
            )
        )
    finally:
        if args.url:
            # Remote snapshots aren't under base_dir; GC them explicitly.
            from torchsnapshot_tpu import Snapshot

            for n in ns:
                for suffix in ("", "-inc"):
                    try:
                        Snapshot(
                            f"{args.url.rstrip('/')}/snap-{n}{suffix}"
                        ).delete(force=True)
                    except Exception:
                        pass
        if args.work_dir is None:
            shutil.rmtree(base_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
