"""Ulysses-style all-to-all sequence parallelism.

The second of the two long-context strategies (the other is ring
attention, `ring_attention.py`): instead of streaming K/V slices around
a ring, one `all_to_all` over the mesh's sequence axis re-partitions
[B, H, S/n, D] activations into [B, H/n, S, D] — every device then holds
the FULL sequence for its head subset, runs ordinary (fused/flash)
attention locally, and a second all_to_all restores the sequence-sharded
layout. Causality is exact by construction (no chunk scheduling, no
zigzag balancing needed — each device computes a complete causal
attention), and the per-device attention can be the fused Pallas kernel
directly, since the full sequence is local.

Trade-offs vs the ring (both exact):

- Communication: Ulysses moves each tensor once — Q and O at
  B·H·S·D/n bytes per device, K and V at B·Hkv·S·D/n; the ring moves
  K/V n−1 times (2·(n−1)·B·Hkv·S/n·D) but overlaps the hops with chunk
  compute. Under GQA the ring's entire volume shrinks by the group
  factor while only Ulysses' K/V half does (Q/O stay full-width) — the
  crossover is workload-dependent, which is why both strategies ship.
- Constraint: Ulysses needs heads divisible by the mesh axis
  (H % n == 0, and Hkv % n == 0 under GQA); the ring needs sequence
  divisibility only. Memory per device is O(B·H·S·D/n) either way.

Layout contract matches the ring: q/k/v are [B, H, S, D] with the
sequence dim sharded over ``axis``; the output has the same sharding.
Differentiable end to end (all_to_all transposes to all_to_all; the
local attention is the flash kernel's custom VJP or the einsum path).
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import (
    _reference_attention,
    flash_attention,
    resolve_flash_block,
    resolve_interpret,
)
from .ring_attention import _resolve_spec


def ulysses_attention(
    q: jax.Array,  # [B, H, S, D], S sharded over `axis`
    k: jax.Array,  # [B, Hkv, S, D]
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    spec: Optional[P] = None,
    attn_impl: str = "flash",
) -> jax.Array:
    """Exact attention over sequence-sharded Q/K/V via head/sequence
    all-to-all re-partitioning (DeepSpeed-Ulysses style), TPU-native:
    `shard_map` + `lax.all_to_all` over ICI.

    ``attn_impl``: "flash" (fused Pallas kernel on the full local
    sequence) or "einsum" (the dense numerical reference).
    """
    if attn_impl not in ("einsum", "flash"):
        raise ValueError(f"unknown attn_impl: {attn_impl!r}")
    b, h, s, d = q.shape
    hkv = k.shape[1]
    n = mesh.shape[axis]
    if s % n:
        raise ValueError(
            f"sequence length {s} must be divisible by {axis}={n}"
        )
    if h % n or hkv % n:
        raise ValueError(
            f"ulysses needs heads divisible by the {axis} axis: "
            f"H={h}, Hkv={hkv}, {axis}={n}. Use ring attention for "
            f"head counts the mesh axis does not divide."
        )
    spec = _resolve_spec(q, axis, spec)
    if attn_impl == "flash":
        flash_block = resolve_flash_block(s)
        flash_interpret = resolve_interpret()

    def local(qc, kc, vc):
        # qc: [B, H_local, S/n, D]. H_local may already be divided by a
        # head-sharding axis (tp); the all_to_all needs the LOCAL head
        # count divisible too — shapes are static at trace time, so this
        # raises at jit/shard_map trace, not at runtime.
        if qc.shape[1] % n or kc.shape[1] % n:
            raise ValueError(
                f"ulysses: per-device head counts ({qc.shape[1]} q, "
                f"{kc.shape[1]} kv after any head sharding) must be "
                f"divisible by {axis}={n}"
            )
        # all_to_all splits the head dim n ways and concatenates the
        # sequence dim: -> [B, H_local/n, S, D] (full sequence, head
        # subset).
        qh = jax.lax.all_to_all(qc, axis, split_axis=1, concat_axis=2, tiled=True)
        kh = jax.lax.all_to_all(kc, axis, split_axis=1, concat_axis=2, tiled=True)
        vh = jax.lax.all_to_all(vc, axis, split_axis=1, concat_axis=2, tiled=True)
        if attn_impl == "flash":
            out = flash_attention(
                qh, kh, vh, causal=causal,
                block_q=flash_block, block_k=flash_block,
                interpret=flash_interpret,
            )
        else:
            g = qh.shape[1] // kh.shape[1]
            out = _reference_attention(
                qh,
                jnp.repeat(kh, g, axis=1) if g > 1 else kh,
                jnp.repeat(vh, g, axis=1) if g > 1 else vh,
                causal,
            )
        # Inverse re-partition: split the sequence, regather the heads.
        return jax.lax.all_to_all(
            out, axis, split_axis=2, concat_axis=1, tiled=True
        )

    shard_fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return shard_fn(q, k, v)
