"""Ring attention: exact attention over sequence-sharded Q/K/V.

Long-context training shards the sequence axis across devices, but
attention needs every query to see every key. Ring attention keeps the
O(S²) score matrix from ever existing globally: each device holds its
[S/n]-slice of Q/K/V, computes block attention against the K/V slice it
currently holds, then passes that slice to its ring neighbor over ICI
(`lax.ppermute`) — n steps later every query has seen every key, with
per-device memory O((S/n)² ) for the live tile and communication
perfectly overlappable with compute. The online-softmax recurrence (the
same one as ops/attention.py's fused kernel) makes the streamed
accumulation exact, not approximate.

This is the sequence-parallel strategy the task's long-context demand
calls for, expressed the TPU way: `shard_map` over the mesh's sequence
axis with XLA collectives, not host-side message passing. Causality is
handled per (query-chunk, key-chunk) pair: key chunks strictly in the
future are skipped via `lax.cond` (no FLOPs), the diagonal chunk gets a
triangular mask, the past is unmasked.

Layout: q, k, v are [B, H, S, D] jax.Arrays sharded P(None, None, axis,
None) over `mesh`; the result has the same sharding. The reference
einsum path (ops/attention.py `_reference_attention`) is the numerical
spec; see tests/test_ring_attention.py.

Causal load balance: under the contiguous layout (`ring_attention`) the
device holding the last sequence chunk computes n chunk-attentions while
device 0 computes one, and each ring step barriers on the ppermute — so
causal wall-clock tracks the busiest device (~2× a balanced layout).
`ring_attention_zigzag` fixes this: device j holds sub-chunks j and
2n-1-j (`to_zigzag`/`from_zigzag` permute at the loop boundary), making
per-device causal work constant while staying exact w.r.t. the original
token order.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def _chunk_attn(q, k, v, scale, mask):
    """Block attention of one (q-chunk, k-chunk) pair.

    Returns (unnormalized_out [Bq, D] rows scaled by exp(s - m), row max
    m [Bq, 1], row denominator l [Bq, 1]) for the online-softmax merge.
    q: [B, Hq, Sq, D]; k, v: [B, Hkv, Sk, D] with Hq % Hkv == 0 (GQA:
    q-head h attends kv-head h // group; the grouped einsum never
    materializes K/V per q-head); mask: [Sq, Sk] bool or None.
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hq % hkv:
        raise ValueError(
            f"query heads ({hq}) must be a multiple of kv heads ({hkv})"
        )
    g = hq // hkv
    qg = q.reshape(b, hkv, g, sq, d)
    s = (
        jnp.einsum("bhgqd,bhkd->bhgqk", qg, k).astype(jnp.float32) * scale
    )  # [B, Hkv, G, Sq, Sk]
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B, Hkv, G, Sq, 1]
    # A fully-masked row (possible only pre-merge) has m == -inf; guard
    # the exp so it contributes zeros, not NaNs.
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask[None, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return (
        o.reshape(b, hq, sq, d),
        m_safe.reshape(b, hq, sq, 1),
        l.reshape(b, hq, sq, 1),
    )


def _merge(acc, o, m_new, l_new):
    """Merge a chunk's (o, m, l) into the running (o, m, l)."""
    o_run, m_run, l_run = acc
    m = jnp.maximum(m_run, m_new)
    alpha = jnp.exp(m_run - m)
    beta = jnp.exp(m_new - m)
    return (o_run * alpha + o * beta, m, l_run * alpha + l_new * beta)


def _infer_spec_padded(
    x: jax.Array, spec: Optional[P], ndim: int = 4
) -> Optional[P]:
    """``spec`` if given, else the array's NamedSharding spec, padded to
    ``ndim`` entries; None when unavailable (e.g. tracers hide
    ``.sharding``)."""
    if spec is None:
        try:
            sharding = x.sharding
        # Tracers hide .sharding; "no spec" degrades to the unsharded
        # path, which is correct just slower.
        except Exception:  # snapcheck: disable=swallowed-exception -- tracer probe
            sharding = None
        if isinstance(sharding, NamedSharding) and sharding.spec:
            spec = sharding.spec
    if spec is None:
        return None
    return P(*(tuple(spec) + (None,) * (ndim - len(spec))))


def _resolve_spec(
    q: jax.Array, axis: str, spec: Optional[P]
) -> P:
    """Preserve the inputs' full layout (e.g. batch sharded over "dp"):
    hardcoding P(None, None, axis, None) would silently all-gather the
    batch and return it replicated. The sequence dim must ride exactly
    `axis` (the ring-position arithmetic assumes it). Inside a trace
    (grad/jit), ``.sharding`` is unavailable — pass ``spec`` explicitly
    there; bare default otherwise."""
    spec = _infer_spec_padded(q, spec)
    if spec is None:
        return P(None, None, axis, None)
    seq_entry = spec[2]
    seq_axes = seq_entry if isinstance(seq_entry, tuple) else (seq_entry,)
    if seq_axes != (axis,):
        raise ValueError(
            f"q's sequence dim is sharded {seq_entry!r}; ring "
            f"attention requires it sharded exactly over {axis!r}"
        )
    return spec


def _rotate(x: jax.Array, axis: str, n: int) -> jax.Array:
    """Send this device's slice to its ring successor."""
    return jax.lax.ppermute(x, axis, [(j, (j + 1) % n) for j in range(n)])


def _norm(acc):
    """Normalize an online-softmax accumulator; guard all-masked rows."""
    o_run, _, l_run = acc
    return o_run / jnp.where(l_run == 0.0, 1.0, l_run)


def ring_attention(
    q: jax.Array,  # [B, H, S, D], S sharded over `axis`
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    causal: bool = True,
    spec: Optional[P] = None,
    chunk_impl: str = "einsum",
) -> jax.Array:
    """Exact softmax(QKᵀ/√D)·V with Q/K/V sequence-sharded over a mesh
    axis; K/V slices rotate around the ring via ppermute.

    ``chunk_impl`` selects the per-chunk attention: ``"einsum"``
    (default) or ``"flash"`` — the fused Pallas kernel per
    (q-chunk, k-chunk) tile, composing ring (cross-device O(S/n) memory)
    with flash (on-device O(chunk·D) memory) for long context. Both are
    differentiable: the flash chunk carries a custom VJP whose backward
    reuses the tiled Pallas kernels (ops/attention.py
    ``flash_chunk_attention``), so long-context *training* keeps the
    fused kernel's memory bound. A flash chunk's normalized output and
    log-sum-exp slot into the online-softmax merge as (out, lse, 1)."""
    if chunk_impl not in ("einsum", "flash"):
        raise ValueError(f"unknown chunk_impl: {chunk_impl!r}")
    b, h, s, d = q.shape
    n = mesh.shape[axis]
    if s % n:
        raise ValueError(
            f"sequence length {s} must be divisible by {axis}={n}"
        )
    chunk = s // n
    scale = 1.0 / (d**0.5)
    spec = _resolve_spec(q, axis, spec)
    if chunk_impl == "flash":
        from ..ops.attention import (
            flash_chunk_attention,
            resolve_flash_block,
            resolve_interpret,
        )

        flash_block = resolve_flash_block(chunk)
        flash_interpret = resolve_interpret()

    def local(qc, kc, vc):
        # qc/kc/vc: this device's local slice — batch/head dims may be
        # sharded over other mesh axes; the seq dim is exactly `chunk`.
        my_idx = jax.lax.axis_index(axis)
        b_local, h_local = qc.shape[0], qc.shape[1]

        tri = jnp.tril(jnp.ones((chunk, chunk), dtype=bool))

        def chunk_triplet(k_cur, v_cur, causal_chunk: bool):
            """(o, m, l) of qc attending to this K/V chunk. The flash
            kernel's (normalized out, lse) is the triple (out, lse, 1):
            out·e^lse = Σ exp(s)·v and 1·e^lse = Σ exp(s), so the merge
            recurrence is unchanged."""
            if chunk_impl == "flash":
                out, lse = flash_chunk_attention(
                    qc, k_cur, v_cur, causal_chunk,
                    flash_block, flash_block, flash_interpret,
                )
                return (
                    out.astype(jnp.float32),
                    lse,
                    jnp.ones_like(lse),
                )
            return _chunk_attn(
                qc, k_cur, v_cur, scale, tri if causal_chunk else None
            )

        def accumulate(i, acc, k_cur, v_cur):
            o_run, m_run, l_run = acc
            # After i rotations of send-to-next, this device holds the
            # K/V chunk originally owned by device (my_idx - i) mod n.
            src = (my_idx - i) % n

            def attend(causal_chunk):
                o, m, l = chunk_triplet(k_cur, v_cur, causal_chunk)
                return _merge((o_run, m_run, l_run), o, m, l)

            if not causal:
                return attend(False)
            return jax.lax.cond(
                src < my_idx,
                lambda: attend(False),  # fully in the past
                lambda: jax.lax.cond(
                    src == my_idx,
                    lambda: attend(True),  # diagonal chunk
                    lambda: (o_run, m_run, l_run),  # future: skip
                ),
            )

        def step(i, carry):
            acc = carry[:3]
            k_cur, v_cur = carry[3], carry[4]
            acc = accumulate(i, acc, k_cur, v_cur)
            return (*acc, _rotate(k_cur, axis, n), _rotate(v_cur, axis, n))

        o0 = jnp.zeros((b_local, h_local, chunk, d), jnp.float32)
        m0 = jnp.full((b_local, h_local, chunk, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b_local, h_local, chunk, 1), jnp.float32)
        # Rotate only between chunk computations: n-1 looped steps that
        # each compute-then-rotate, then the last chunk outside the loop
        # (rotating after it would be a discarded ICI hop).
        carry = jax.lax.fori_loop(0, n - 1, step, (o0, m0, l0, kc, vc))
        acc = accumulate(n - 1, carry[:3], carry[3], carry[4])
        return _norm(acc).astype(qc.dtype)

    shard_fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return shard_fn(q, k, v)


def shard_seq(x: jax.Array, mesh: Mesh, axis: str = "sp") -> jax.Array:
    """Place [B, H, S, D] with the sequence dim sharded over `axis`."""
    return jax.device_put(x, NamedSharding(mesh, P(None, None, axis, None)))


# --------------------------------------------------------------- zigzag

def zigzag_indices(s: int, n: int) -> jnp.ndarray:
    """Token permutation for the balanced causal layout: device j holds
    sub-chunks j and 2n-1-j of size s/(2n). Summed causal work per
    device is then constant ((j+1) + (2n-j) sub-chunk attentions), so no
    device waits ~2× on the busiest one (the contiguous layout's
    imbalance, see module docstring). Returns indices such that
    ``x[..., idx, :]`` is in zigzag order."""
    if s % (2 * n):
        raise ValueError(
            f"sequence length {s} must be divisible by 2*n={2 * n}"
        )
    c = s // (2 * n)
    order = []
    for j in range(n):
        order.extend(range(j * c, (j + 1) * c))
        order.extend(range((2 * n - 1 - j) * c, (2 * n - j) * c))
    return jnp.asarray(order, jnp.int32)


def _zigzag_target_spec(
    x: jax.Array, axis: str, spec: Optional[P], seq_axis: int
) -> P:
    """Keep the input's batch/head shardings (a bare seq-only spec would
    silently all-gather a dp-sharded batch); only the sequence dim is
    forced onto `axis`. Pass ``spec`` explicitly under jit/grad (tracers
    hide ``.sharding`` and the fallback would drop the batch sharding)."""
    inferred = _infer_spec_padded(x, spec, ndim=x.ndim)
    entries = [None] * x.ndim if inferred is None else list(inferred)
    entries[seq_axis] = axis
    return P(*entries)


def to_zigzag(
    x: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    spec: Optional[P] = None,
    seq_axis: int = 2,
) -> jax.Array:
    """Permute ``x`` into zigzag order along its sequence dimension and
    shard that dim over `axis` (other dims keep their shardings).

    ``seq_axis`` defaults to 2 ([B, H, S, D] attention tensors); pass 1
    for [B, S]-shaped tokens or [B, S, V] logits."""
    idx = zigzag_indices(x.shape[seq_axis], mesh.shape[axis])
    target = _zigzag_target_spec(x, axis, spec, seq_axis)
    return jax.device_put(
        jnp.take(x, idx, axis=seq_axis), NamedSharding(mesh, target)
    )


def from_zigzag(
    x: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    spec: Optional[P] = None,
    seq_axis: int = 2,
) -> jax.Array:
    """Invert :func:`to_zigzag` (shardings preserved)."""
    idx = zigzag_indices(x.shape[seq_axis], mesh.shape[axis])
    inv = jnp.argsort(idx)
    target = _zigzag_target_spec(x, axis, spec, seq_axis)
    return jax.device_put(
        jnp.take(x, inv, axis=seq_axis), NamedSharding(mesh, target)
    )


def ring_attention_zigzag(
    q: jax.Array,  # [B, H, S, D] in ZIGZAG token order, S sharded on axis
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    spec: Optional[P] = None,
    chunk_impl: str = "einsum",
) -> jax.Array:
    """Causal ring attention over zigzag-ordered inputs (balanced work).

    Inputs and output are in zigzag token order (use
    :func:`to_zigzag`/:func:`from_zigzag` at the loop boundary — training
    loops keep all sequence tensors zigzag-ordered so the permutes happen
    once at data loading, not per step). Causality is enforced w.r.t. the
    ORIGINAL token order via global sub-chunk ids. ``chunk_impl`` as in
    :func:`ring_attention`; both paths are differentiable (the flash
    sub-chunk rides ``flash_chunk_attention``'s custom VJP).
    """
    if chunk_impl not in ("einsum", "flash"):
        raise ValueError(f"unknown chunk_impl: {chunk_impl!r}")
    b, h, s, d = q.shape
    n = mesh.shape[axis]
    if s % (2 * n):
        raise ValueError(
            f"sequence length {s} must be divisible by 2*{axis}={2 * n}"
        )
    c = s // (2 * n)  # sub-chunk length
    scale = 1.0 / (d**0.5)
    spec = _resolve_spec(q, axis, spec)
    if chunk_impl == "flash":
        from ..ops.attention import (
            flash_chunk_attention,
            resolve_flash_block,
            resolve_interpret,
        )

        flash_block = resolve_flash_block(c)
        flash_interpret = resolve_interpret()

    def local(qc, kc, vc):
        my = jax.lax.axis_index(axis)
        # Local halves and their global sub-chunk ids.
        q_lo, q_hi = qc[:, :, :c], qc[:, :, c:]
        q_ids = (my, 2 * n - 1 - my)

        tri = jnp.tril(jnp.ones((c, c), dtype=bool))

        def sub_step(acc, q_sub, q_id, k_sub, v_sub, k_id):
            def attend(causal_sub: bool):
                if chunk_impl == "flash":
                    out, lse = flash_chunk_attention(
                        q_sub, k_sub, v_sub, causal_sub,
                        flash_block, flash_block, flash_interpret,
                    )
                    return _merge(
                        acc, out.astype(jnp.float32), lse, jnp.ones_like(lse)
                    )
                o, m, l = _chunk_attn(
                    q_sub, k_sub, v_sub, scale, tri if causal_sub else None
                )
                return _merge(acc, o, m, l)

            return jax.lax.cond(
                k_id < q_id,
                lambda: attend(False),
                lambda: jax.lax.cond(
                    k_id == q_id, lambda: attend(True), lambda: acc
                ),
            )

        def accumulate_both(i, acc_lo, acc_hi, k_cur, v_cur):
            src = (my - i) % n
            for half, k_id in ((0, src), (1, 2 * n - 1 - src)):
                k_sub = k_cur[:, :, half * c : (half + 1) * c]
                v_sub = v_cur[:, :, half * c : (half + 1) * c]
                acc_lo = sub_step(acc_lo, q_lo, q_ids[0], k_sub, v_sub, k_id)
                acc_hi = sub_step(acc_hi, q_hi, q_ids[1], k_sub, v_sub, k_id)
            return acc_lo, acc_hi

        def step(i, carry):
            acc_lo, acc_hi, k_cur, v_cur = carry
            acc_lo, acc_hi = accumulate_both(i, acc_lo, acc_hi, k_cur, v_cur)
            return (acc_lo, acc_hi, _rotate(k_cur, axis, n), _rotate(v_cur, axis, n))

        def init():
            bl, hl = qc.shape[0], qc.shape[1]
            return (
                jnp.zeros((bl, hl, c, d), jnp.float32),
                jnp.full((bl, hl, c, 1), _NEG_INF, jnp.float32),
                jnp.zeros((bl, hl, c, 1), jnp.float32),
            )

        carry = jax.lax.fori_loop(0, n - 1, step, (init(), init(), kc, vc))
        acc_lo, acc_hi = accumulate_both(n - 1, carry[0], carry[1], carry[2], carry[3])
        return jnp.concatenate(
            [_norm(acc_lo), _norm(acc_hi)], axis=2
        ).astype(qc.dtype)

    shard_fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return shard_fn(q, k, v)
