"""The three links the product moves bytes over, probed in the same run
on the same devices and directory as the saves they are held against.

Each probe moves data the way the pipeline does, so that the pipeline
cannot beat its own ceiling: D2H as 8 MiB device slices fetched by 32
threads (``ops/transfer.parallel_device_get``), H2D as a batch of
16 MiB puts joined on the device (``chunked_device_put``), storage as
objects written tmp + fsync + rename by 16 writers (``storage_plugins/
fs.py`` under ``scheduler.py``). Best of three passes: interference only
subtracts. Sizes are pinned here, not taken from the environment.

The idea is that of ``ops/transfer.probe_h2d_gbps``, which reads
``jax.devices()[0]`` only; the H2D probe here is the benchmark's own, so
that the program cannot change the ceiling it is held against.
"""

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

import numpy as np

PROBE_BYTES_PER_DEVICE = 512 * 1024 * 1024
D2H_SLICE_BYTES = 8 * 1024 * 1024
D2H_THREADS = 32
H2D_CHUNK_BYTES = 16 * 1024 * 1024
STORAGE_PROBE_BYTES = 512 * 1024 * 1024
STORAGE_OBJECT_BYTES = 64 * 1024 * 1024
STORAGE_WRITERS = 16
PASSES = 3


def _best(fn, total_bytes: int) -> float:
    best = 0.0
    for _ in range(PASSES):
        begin = time.monotonic()
        fn()
        best = max(best, total_bytes / (time.monotonic() - begin))
    return best / 1e9


def probe_d2h_gbps(devices: List[Any], nbytes: int = PROBE_BYTES_PER_DEVICE) -> float:
    """GB/s off all ``devices`` together, one array a device."""
    import jax
    import jax.numpy as jnp

    rows = max(2, nbytes // D2H_SLICE_BYTES)
    cols = max(1, nbytes // rows // 4)
    arrays = [
        jax.device_put(jnp.full((rows, cols), float(i + 1), jnp.float32), d)
        for i, d in enumerate(devices)
    ]
    jax.block_until_ready(arrays)
    out = [np.empty((rows, cols), np.float32) for _ in arrays]

    # One program for every slice (the pipeline compiles one a chunk;
    # the transfer is the same).
    take_row = jax.jit(lambda a, row: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=0))

    def fetch(which: int, row: int) -> None:
        piece = take_row(arrays[which], np.int32(row))
        out[which][row : row + 1] = np.asarray(piece)

    with ThreadPoolExecutor(D2H_THREADS) as pool:

        def one_pass() -> None:
            futures = [
                pool.submit(fetch, which, row)
                for row in range(rows)
                for which in range(len(arrays))
            ]
            for f in futures:
                f.result()

        one_pass()  # compiles the slices; not timed
        gbps = _best(one_pass, rows * cols * 4 * len(arrays))
    for a in arrays:
        a.delete()
    return gbps


def probe_h2d_gbps(devices: List[Any], nbytes: int = PROBE_BYTES_PER_DEVICE) -> float:
    """GB/s onto all ``devices`` together. Every pass puts a host buffer
    that has not been put before: re-putting one measures a cached
    staging path, not a restore."""
    import jax
    import jax.numpy as jnp

    chunk = max(1, min(H2D_CHUNK_BYTES, nbytes) // 4)
    n_chunks = max(1, nbytes // 4 // chunk)
    fresh = [
        np.full(n_chunks * chunk, float(i + 1), np.float32)
        for i in range(PASSES + 1)
    ]
    force = jax.jit(lambda parts: jnp.sum(jnp.concatenate(parts)[::4096]))

    def one_pass() -> None:
        host = fresh.pop()
        pieces = [host[i * chunk : (i + 1) * chunk] for i in range(n_chunks)]
        landed = [jax.device_put(pieces, [d] * n_chunks) for d in devices]
        for parts in landed:
            float(force(parts))
        for parts in landed:
            for p in parts:
                p.delete()

    one_pass()  # compiles the join; not timed
    return _best(one_pass, n_chunks * chunk * 4 * len(devices))


def probe_storage_write_gbps(root: str, nbytes: int = STORAGE_PROBE_BYTES) -> float:
    """GB/s of durable object writes into ``root`` (the run's own):
    every object goes to a temporary name, is fsynced and renamed."""
    directory = os.path.join(root, ".probe")
    os.makedirs(directory, exist_ok=True)
    object_bytes = min(STORAGE_OBJECT_BYTES, nbytes)
    n_objects = max(1, nbytes // object_bytes)
    payload = np.arange(object_bytes, dtype=np.uint8).tobytes()

    def write(i: int) -> None:
        final = os.path.join(directory, f"object-{i}")
        tmp = final + ".tmp"
        with open(tmp, "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)

    with ThreadPoolExecutor(STORAGE_WRITERS) as pool:

        def one_pass() -> None:
            for f in [pool.submit(write, i) for i in range(n_objects)]:
                f.result()

        gbps = _best(one_pass, n_objects * object_bytes)
    for name in os.listdir(directory):
        os.remove(os.path.join(directory, name))
    os.rmdir(directory)
    return gbps


def run_probes(devices: List[Any], root: str, scale: float = 1.0) -> Dict[str, float]:
    """``scale`` below 1 shrinks every probe (the tests' toy runs)."""
    return {
        "d2h_gbps": probe_d2h_gbps(devices, int(PROBE_BYTES_PER_DEVICE * scale)),
        "h2d_gbps": probe_h2d_gbps(devices, int(PROBE_BYTES_PER_DEVICE * scale)),
        "storage_write_gbps": probe_storage_write_gbps(
            root, int(STORAGE_PROBE_BYTES * scale)
        ),
    }
