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


def _leaf_sums(x, offsets=None, shape=None):
    """The two sums of ``x``; where ``x`` is a block of a larger leaf,
    ``offsets`` is where it starts in the leaf and ``shape`` the leaf's,
    so that each element is weighted by its place in the leaf."""
    import jax
    import jax.numpy as jnp

    shape = x.shape if shape is None else shape
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
        place = jax.lax.broadcasted_iota(jnp.uint32, x.shape, axis)
        if offsets is not None and offsets[axis]:
            place = place + jnp.uint32(offsets[axis] % 2**32)
        index = index + place * jnp.uint32(stride % 2**32)
        stride *= shape[axis]
    weights = index * jnp.uint32(_MIX) + jnp.uint32(1)
    return jnp.stack([jnp.sum(words), jnp.sum(words * weights)])


def make_checksum_fn():
    """A jitted ``tree -> uint32[n_leaves, 2]``; sums wrap mod 2**32."""
    import jax
    import jax.numpy as jnp

    def checksums(tree):
        return jnp.stack([_leaf_sums(x) for x in jax.tree.leaves(tree)])

    return jax.jit(checksums)


def make_copy_checksum_fn():
    """A ``tree -> pending`` that sums every copy of every leaf.

    A leaf replicated over a mesh axis is held once on each device along
    it, and a whole-array sum reads one of those copies. Here every
    device sums each block it holds, weighted by the block's place in
    the leaf: one jitted call a device, dispatched and not waited for.
    ``copy_sums`` adds the blocks of each copy (a shard's
    ``replica_id``) up to that copy's ``uint32[2]``; the blocks of one
    copy cover the leaf once, and the sums wrap mod 2**32, so a copy
    that holds the leaf's bits gives the leaf's sums."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def block_sums(blocks, places):
        return jnp.stack([_leaf_sums(b, *place) for b, place in zip(blocks, places)])

    def dispatch(tree):
        held = {}
        for i, leaf in enumerate(jax.tree.leaves(tree)):
            for shard in leaf.addressable_shards:
                offsets = tuple(s.start or 0 for s in shard.index)
                held.setdefault(shard.device, []).append(
                    ((i, shard.replica_id, shard.device.id), shard.data, (offsets, leaf.shape))
                )
        return [
            (
                [key for key, _, _ in blocks],
                block_sums([b for _, b, _ in blocks], tuple(p for _, _, p in blocks)),
            )
            for blocks in held.values()
        ]

    return dispatch


def copy_sums(pending) -> Dict[Any, Any]:
    """``{(leaf index, replica_id): (uint32[2], [device ids])}`` from what
    ``make_copy_checksum_fn``'s callable returned; fetches the sums."""
    copies: Dict[Any, Any] = {}
    for keys, sums in pending:
        for (leaf, replica, device), row in zip(keys, np.asarray(sums)):
            total, devices = copies.get((leaf, replica), (np.zeros(2, np.uint64), []))
            copies[(leaf, replica)] = (
                (total + row.astype(np.uint64)) % 2**32,
                devices + [device],
            )
    return {k: (total.astype(np.uint32), sorted(d)) for k, (total, d) in copies.items()}


def differing_copies(
    names: Sequence[str], pinned: np.ndarray, copies: Dict[Any, Any]
) -> List[Dict[str, Any]]:
    """One entry for every copy of a leaf whose sums are not the pinned
    ones; an empty list means every copy of every leaf is bit-identical
    to what was saved."""
    pinned = np.asarray(pinned)
    held = {leaf for leaf, _ in copies}
    if held != set(range(len(names))) or len(pinned) != len(names):
        return [
            {
                "leaf": "<tree>",
                "pinned_at_save": f"{len(names)} leaves, sums {pinned.shape}",
                "restored": f"copies of {len(held)} leaves",
            }
        ]
    return [
        {
            "leaf": names[leaf],
            "copy": replica,
            "devices": devices,
            "pinned_at_save": [int(v) for v in pinned[leaf]],
            "restored": [int(v) for v in got],
        }
        for (leaf, replica), (got, devices) in sorted(copies.items())
        if not np.array_equal(pinned[leaf], got)
    ]


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
