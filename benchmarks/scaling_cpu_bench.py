"""Multi-process scaling evidence (VERDICT r4 #5).

The reference's headline is near-linear Snapshot.take speedup 1→32
workers for replicated state (reference benchmarks/ddp/README.md:13-19),
which comes from striping replicated writes across ranks. This script
spawns REAL process worlds (1/2/4/8) coordinating through a FileStore
and records, per world size:

- **replicated**: per-rank written bytes (the LPT size-balanced striping
  — each rank should carry ~1/N of the bytes, balanced), and per-rank
  take wall-clock measured INSIDE the workers (spawn + jax-import
  overhead excluded);
- **sharded**: a global array sharded across all processes via
  ``jax.distributed`` (one virtual CPU device per process), each rank
  persisting only its addressable shards.

Caveat recorded in the JSON: on a single-core host N processes contend
one CPU, so WALL-clock need not shrink with world size even though
per-rank work provably does (bytes/rank ∝ 1/N). ``cpu_count`` is
included so readers can interpret the wall numbers; on multi-core
hosts the replicated take time shrinks like the reference's.

Invoked by bench.py as a subprocess with JAX_PLATFORMS=cpu; prints ONE
JSON line on stdout.
"""

import json
import os
import shutil
import sys
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

_N_PARAMS = 24


def _total_bytes() -> int:
    return int(
        os.environ.get("TPUSNAPSHOT_SCALING_BENCH_BYTES", 256 * 1024**2)
    )


def _worker_replicated(rank, nprocs, store_path, snap_path, out_dir):
    # N workers on one host can never share a chip: pinned to CPU.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.coord import FileStore, StoreCoordinator

    class _Holder:
        def __init__(self, sd):
            self.sd = sd

        def state_dict(self):
            return self.sd

        def load_state_dict(self, sd):
            self.sd = sd

    coord = StoreCoordinator(FileStore(store_path), rank, nprocs, timeout_s=300)
    param_bytes = _total_bytes() // _N_PARAMS
    rng = np.random.default_rng(0)  # identical on every rank (DDP state)
    sd = {
        f"p{i}": rng.standard_normal(param_bytes // 8) for i in range(_N_PARAMS)
    }
    coord.barrier()
    begin = time.monotonic()
    Snapshot.take(snap_path, {"m": _Holder(sd)}, coord=coord, replicated=["**"])
    elapsed = time.monotonic() - begin
    with open(os.path.join(out_dir, f"t{rank}"), "w") as f:
        f.write(str(elapsed))


def _worker_sharded(rank, nprocs, store_path, snap_path, out_dir, port):
    import os as _os

    _os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    _os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_REPO_ROOT)
    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=nprocs,
        process_id=rank,
    )
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.coord import FileStore, StoreCoordinator

    class _Holder:
        def __init__(self, sd):
            self.sd = sd

        def state_dict(self):
            return self.sd

        def load_state_dict(self, sd):
            self.sd = sd

    n_rows = _total_bytes() // (4 * 1024)
    mesh = Mesh(np.array(jax.devices()), ("x",))
    sharding = NamedSharding(mesh, P("x", None))
    global_shape = (n_rows, 1024)
    local_arrays = []
    for d, idx in sharding.addressable_devices_indices_map(global_shape).items():
        rows = range(*idx[0].indices(n_rows))
        rng = np.random.default_rng(rows.start)
        block = rng.standard_normal(
            ((rows.stop - rows.start), 1024)
        ).astype(np.float32)
        local_arrays.append(jax.device_put(block, d))
    arr = jax.make_array_from_single_device_arrays(
        global_shape, sharding, local_arrays
    )
    jax.block_until_ready(arr)
    coord = StoreCoordinator(FileStore(store_path), rank, nprocs, timeout_s=300)
    coord.barrier()
    begin = time.monotonic()
    Snapshot.take(snap_path, {"m": _Holder({"w": arr})}, coord=coord)
    elapsed = time.monotonic() - begin
    with open(os.path.join(out_dir, f"t{rank}"), "w") as f:
        f.write(str(elapsed))


def _per_rank_bytes(snap_path, world):
    """Bytes each rank actually persisted, attributed from the merged
    manifest: a replicated entry's stripe owner is the rank whose copy
    carries the checksum (non-owners never stage bytes); sharded/chunked
    entries list each rank's own shards in its namespace."""
    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.manifest import (
        ArrayEntry,
        ShardedArrayEntry,
        is_replicated,
    )
    from torchsnapshot_tpu.serialization import array_nbytes

    manifest = Snapshot(snap_path).get_manifest()
    per_rank = [0] * world
    for path, entry in manifest.items():
        try:
            rank = int(path.split("/", 1)[0])
        except ValueError:
            continue
        if isinstance(entry, ArrayEntry):
            if is_replicated(entry) and entry.checksum is None:
                continue  # another rank's stripe
            per_rank[rank] += array_nbytes(entry.dtype, entry.shape)
        elif isinstance(entry, ShardedArrayEntry):
            for shard in entry.shards:
                if shard.array.checksum is None:
                    continue
                per_rank[rank] += array_nbytes(
                    shard.array.dtype, shard.array.shape
                )
    return per_rank


def _run_world(world, mode, base_dir, port):
    from torchsnapshot_tpu.utils.test_utils import run_multiprocess

    work = os.path.join(base_dir, f"{mode}-{world}")
    os.makedirs(work, exist_ok=True)
    snap = os.path.join(work, "snap")
    store = os.path.join(work, "store")
    if mode == "replicated":
        run_multiprocess(
            _worker_replicated, world, store, args=(snap, work)
        )
    else:
        run_multiprocess(
            _worker_sharded, world, store, args=(snap, work, port)
        )
    times = []
    for r in range(world):
        with open(os.path.join(work, f"t{r}")) as f:
            times.append(float(f.read()))
    per_rank = _per_rank_bytes(snap, world)
    mean = sum(per_rank) / max(1, len([b for b in per_rank if b])) or 1
    result = {
        "world": world,
        "take_s": round(max(times), 3),
        "per_rank_take_s": [round(t, 3) for t in times],
        "per_rank_bytes": per_rank,
        "balance_max_over_mean": round(max(per_rank) / mean, 3),
    }
    shutil.rmtree(work, ignore_errors=True)
    return result


def main() -> None:
    worlds = [
        int(w)
        for w in os.environ.get(
            "TPUSNAPSHOT_SCALING_WORLDS", "1,2,4,8"
        ).split(",")
    ]
    base_dir = tempfile.mkdtemp(prefix="tpusnapshot-scaling-")
    out = {
        "ok": True,
        "bytes": _total_bytes(),
        "cpu_count": os.cpu_count(),
        "replicated": [],
        "sharded": [],
    }
    try:
        port = 12421
        for world in worlds:
            out["replicated"].append(
                _run_world(world, "replicated", base_dir, port)
            )
        for world in worlds:
            if world == 1:
                continue  # sharded over one process is the dense path
            port += 1
            out["sharded"].append(
                _run_world(world, "sharded", base_dir, port)
            )
        # Headline facts asserted, not eyeballed: replicated bytes/rank
        # fall ~1/N and stay balanced.
        for entry in out["replicated"]:
            ideal = _total_bytes() / entry["world"]
            owned = [b for b in entry["per_rank_bytes"] if b > 0]
            if entry["world"] > 1:
                out["ok"] = out["ok"] and len(owned) == entry["world"]
                out["ok"] = out["ok"] and max(owned) <= 2.2 * ideal
    except Exception as e:  # pragma: no cover - evidence must not die silently
        import traceback

        traceback.print_exc(file=sys.stderr)
        out["ok"] = False
        out["error"] = repr(e)
    finally:
        shutil.rmtree(base_dir, ignore_errors=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
