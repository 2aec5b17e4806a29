"""Sharded/subdivided write-path coverage bench (VERDICT r3 #3).

The headline bench runs on ONE real TPU chip, where every parameter is a
dense per-rank array — the ShardedArrayEntry write path, the 512 MiB
subdivision (io_preparer.MAX_CHUNK_SIZE_BYTES), and multi-chunk
resharded restore never appear inside it. This script runs those paths
at scale on an 8-virtual-device CPU mesh (the same mechanism the
multi-chip dryrun uses) so the certified artifact includes a timed
save/restore whose write set contains subdivided chunks.

Invoked by bench.py as a subprocess with JAX_PLATFORMS=cpu; prints ONE
JSON line on stdout. These numbers measure host memory bandwidth + disk,
not the TPU link — they are path-coverage evidence, not the headline.
"""

import json
import math
import os
import shutil
import sys
import tempfile
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)


def main() -> None:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu import Snapshot
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_REPO_ROOT)
    from torchsnapshot_tpu.io_preparer import MAX_CHUNK_SIZE_BYTES
    from torchsnapshot_tpu.manifest import ShardedArrayEntry

    total_bytes = int(
        os.environ.get("TPUSNAPSHOT_SHARDED_BENCH_BYTES", 3 * (512 * 1024**2))
    )
    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"

    # 2-way sharding of `total_bytes` makes each shard exceed the 512 MiB
    # subdivision cap (3 x 512 MiB total -> 768 MiB shards -> 512+256
    # chunks), so the write set contains subdivided chunks by
    # construction — asserted below, not assumed.
    n_rows = total_bytes // (4 * 1024)
    mesh2 = Mesh(np.array(devices[:2]), ("x",))
    arr = jax.device_put(
        jnp.ones((n_rows, 1024), jnp.float32),
        NamedSharding(mesh2, P("x", None)),
    )
    jax.block_until_ready(arr)

    class _Holder:
        def __init__(self, sd):
            self.sd = sd

        def state_dict(self):
            return self.sd

        def load_state_dict(self, sd):
            self.sd = sd

    bench_dir = tempfile.mkdtemp(prefix="tpusnapshot-sharded-bench-")
    try:
        path = f"{bench_dir}/snap"
        begin = time.monotonic()
        Snapshot.take(path, {"m": _Holder({"w": arr})})
        take_s = time.monotonic() - begin

        entry = Snapshot(path).get_manifest()["0/m/w"]
        assert isinstance(entry, ShardedArrayEntry)
        n_chunks = len(entry.shards)
        expected = 2 * math.ceil(
            (total_bytes / 2) / MAX_CHUNK_SIZE_BYTES
        )
        assert n_chunks == expected and n_chunks > 2, (
            f"write set not subdivided: {n_chunks} chunks "
            f"(expected {expected})"
        )

        # Multi-chunk resharded restore: 8-way sharding never seen at
        # save time; every target shard assembles from ranged reads of
        # the subdivided chunks.
        mesh8 = Mesh(np.array(devices), ("x",))
        template = jax.device_put(
            jnp.zeros((n_rows, 1024), jnp.float32),
            NamedSharding(mesh8, P("x", None)),
        )
        jax.block_until_ready(template)
        target = _Holder({"w": template})
        begin = time.monotonic()
        Snapshot(path).restore({"m": target})
        restored = target.sd["w"]
        # Force materialization before stopping the clock.
        float(jax.jit(jnp.sum)(restored))
        restore_s = time.monotonic() - begin
        ok = bool(float(jnp.sum(restored)) == float(n_rows * 1024))

        gib = total_bytes / 1024**3
        print(
            json.dumps(
                {
                    "ok": ok,
                    "bytes": total_bytes,
                    "subdivided_chunks": n_chunks,
                    "take_GBps": round(gib / take_s, 3),
                    "restore_GBps": round(gib / restore_s, 3),
                    "take_s": round(take_s, 2),
                    "restore_s": round(restore_s, 2),
                }
            )
        )
    finally:
        shutil.rmtree(bench_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
