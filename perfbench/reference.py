"""The plain reference that decides ``correct``.

The guarantee under test is that a restore gives back, bit for bit, the
state as it was when the save was called. The reference is therefore
the identity on that state, held as two position-weighted 32-bit sums
per leaf that this file computes on the device from the raw bits of the
arrays (one fused pass over HBM, a few milliseconds for gigabytes),
before the next training step donates them. It imports nothing of the
program and takes nothing the program made: no fingerprint, manifest or
checksum of the library enters the comparison. ``checksums_numpy`` is
the same arithmetic written out in numpy, which the tests hold the
device version against.

A comparison is exact: the limit on the number of leaves that differ is
0. No time, rate or deadline is compared anywhere in this file.
"""

from typing import Any, Dict, List, Sequence

import numpy as np

_MIX = 2654435761  # Knuth's multiplicative constant; odd, so a bijection mod 2**32
_UINTS = {1: "uint8", 2: "uint16", 4: "uint32"}


def leaf_names(tree: Any) -> List[str]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def _leaf_sums(x):
    import jax
    import jax.numpy as jnp

    itemsize = np.dtype(x.dtype).itemsize
    if itemsize not in _UINTS:
        raise TypeError(f"no checksum for {x.dtype} ({itemsize} bytes an item)")
    words = jax.lax.bitcast_convert_type(x, jnp.dtype(_UINTS[itemsize]))
    words = words.astype(jnp.uint32)
    # The flat index of every element, built from per-axis iotas so that
    # a sharded leaf is summed where it lies (no reshape, no gather).
    index = jnp.zeros(x.shape, jnp.uint32)
    stride = 1
    for axis in reversed(range(x.ndim)):
        index = index + jax.lax.broadcasted_iota(
            jnp.uint32, x.shape, axis
        ) * jnp.uint32(stride % 2**32)
        stride *= x.shape[axis]
    weights = index * jnp.uint32(_MIX) + jnp.uint32(1)
    return jnp.stack([jnp.sum(words), jnp.sum(words * weights)])


def make_checksum_fn():
    """A jitted ``tree -> uint32[n_leaves, 2]``; sums wrap mod 2**32."""
    import jax
    import jax.numpy as jnp

    def checksums(tree):
        return jnp.stack([_leaf_sums(x) for x in jax.tree.leaves(tree)])

    return jax.jit(checksums)


def checksums_numpy(leaves: Sequence[np.ndarray]) -> np.ndarray:
    out = []
    for leaf in leaves:
        leaf = np.ascontiguousarray(leaf)
        words = leaf.reshape(-1).view(_UINTS[leaf.dtype.itemsize])
        words = words.astype(np.uint64)
        index = np.arange(words.size, dtype=np.uint64)
        weights = (index * _MIX + 1) % 2**32
        out.append(
            [int(words.sum() % 2**32), int((words * weights % 2**32).sum() % 2**32)]
        )
    return np.array(out, dtype=np.uint32)


def differing_leaves(
    names: Sequence[str], pinned: np.ndarray, restored: np.ndarray
) -> List[Dict[str, Any]]:
    """One entry for every leaf whose restored sums are not the pinned
    ones; an empty list means bit-identical to what was saved."""
    pinned = np.asarray(pinned)
    restored = np.asarray(restored)
    if pinned.shape != restored.shape or len(names) != len(pinned):
        return [
            {
                "leaf": "<tree>",
                "pinned_at_save": f"{len(names)} leaves, sums {pinned.shape}",
                "restored": f"sums {restored.shape}",
            }
        ]
    return [
        {
            "leaf": name,
            "pinned_at_save": [int(v) for v in want],
            "restored": [int(v) for v in got],
        }
        for name, want, got in zip(names, pinned, restored)
        if not np.array_equal(want, got)
    ]
