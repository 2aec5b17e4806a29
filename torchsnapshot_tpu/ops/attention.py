"""Fused (flash) attention Pallas kernel for the flagship transformer.

The transformer workload's hot op is attention; materializing the
[B, H, S, S] score matrix is O(S²) HBM traffic, which is what caps long
sequences. This kernel computes softmax(QKᵀ)·V with the online-softmax
recurrence, tiled so only [block_q, block_k] score tiles ever exist —
they live in VMEM, QKᵀ and P·V run on the MXU, and HBM traffic drops to
O(S·D). Causal masking skips fully-masked key blocks outright
(predicated off, not just masked), halving the work of autoregressive
attention.

Kernel structure (see /opt/skills/guides/pallas_guide.md):
- grid = (batch·heads, S/block_q, S/block_k); the last axis iterates
  sequentially on TPU, so the running max/denominator/accumulator live
  in VMEM scratch that persists across it;
- accumulation in float32 regardless of input dtype (bf16-safe);
- on a TPU backend the kernels compile through Mosaic or the call fails;
  only the CPU backend interprets them (the hermetic test suite), see
  :func:`resolve_interpret`.

The backward pass is also tiled Pallas: the forward saves the per-row
log-sum-exp, and two kernels reconstruct p = exp(s - lse) per tile to
accumulate dq (over key blocks) and dk/dv (over query blocks) — the
score matrix never materializes in either direction, so the O(S·D)
memory bound holds for training too.

Exposed through the transformer via ``TransformerConfig.flash_attention``
(off by default: the einsum path remains the numerical reference; the
kernel reassociates the softmax reduction so results match to float
tolerance, not bitwise).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _causal_positions(qi, kj, block_q: int, block_k: int):
    """Global (q_pos, k_pos) grids for one (q-block, k-block) tile —
    the single source of the position math shared by the forward and
    backward kernels."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return q_pos, k_pos


def _block_visible(qi, kj, block_q: int, block_k: int, window=None, diffusion=None):
    """Whether any key of block kj is visible (causally, and under a
    ``window``: within it; under ``diffusion``: by the block-diffusion
    mask) to some query of block qi."""
    if diffusion is not None:
        return _diffusion_block_visible(qi, kj, block_q, block_k, *diffusion)
    visible = kj * block_k <= qi * block_q + (block_q - 1)
    if window is None:
        return visible
    # The block's last key lies within the window of its first query.
    return visible & (qi * block_q - (kj * block_k + block_k - 1) < window)


def _visible(q_pos, k_pos, window=None, diffusion=None):
    """The causal mask of one tile; under a ``window`` query i sees the
    keys j with ``0 <= i - j < window``; under ``diffusion`` the mask is
    :func:`_diffusion_visible`'s and not causal by token."""
    if diffusion is not None:
        return _diffusion_visible(q_pos, k_pos, *diffusion)
    if window is None:
        return k_pos <= q_pos
    return (k_pos <= q_pos) & (q_pos - k_pos < window)


def _diffusion_visible(q_pos, k_pos, half: int, block: int):
    """The mask of block-diffusion training over a sequence of two
    halves of ``half`` tokens each, the clean tokens first and their
    noised copies after them, both in blocks of ``block``: a clean query
    sees the clean keys of its own block and of those before it; a
    noised query sees the clean keys of the blocks strictly before its
    own and the noised keys of its own block; no clean query sees a
    noised key. Positions are integer arrays (or numpy's) that
    broadcast against each other."""
    q_noised, k_noised = q_pos >= half, k_pos >= half
    q_blk = _block_of(q_pos - q_noised * half, block)
    k_blk = _block_of(k_pos - k_noised * half, block)
    # a noised query's own block is hidden among the clean keys
    from_clean = ~k_noised & (k_blk <= q_blk - q_noised)
    return from_clean | (q_noised & k_noised & (k_blk == q_blk))


def block_diffusion_mask(half: int, block: int):
    """:func:`_diffusion_visible` written out for the whole sequence:
    bool [2 half, 2 half] (numpy), (query, key) -> seen. For an einsum
    path and for tests; the kernels never build it."""
    at = np.arange(2 * half)
    return _diffusion_visible(at[:, None], at[None, :], half, block)


def _diffusion_block_visible(qi, kj, block_q: int, block_k: int, half: int, block: int):
    """Whether :func:`_diffusion_visible` is true anywhere in the tile
    (qi, kj), from the tile's corners alone. A tile may straddle the
    two halves."""
    q0, q1 = qi * block_q, qi * block_q + (block_q - 1)
    k0, k1 = kj * block_k, kj * block_k + (block_k - 1)
    last = half - 1
    has_clean_k = k0 < half
    first_clean_blk = _block_of(k0, block)
    # clean queries (q0 .. min(q1, half - 1)) on clean keys
    clean = (
        (q0 < half) & has_clean_k
        & (first_clean_blk <= _block_of(jnp.minimum(q1, last), block))
    )
    # noised queries: the blocks a .. c of their positions within the
    # noised half, and ka .. kc those of the tile's noised keys (a tile
    # that holds none is ruled out by the comparisons with ``half``)
    in_half = lambda pos: _block_of(jnp.maximum(pos, half) - half, block)
    a, c, ka, kc = in_half(q0), in_half(q1), in_half(k0), in_half(k1)
    on_clean = has_clean_k & (first_clean_blk < c)
    on_noised = (k1 >= half) & (ka <= c) & (kc >= a)
    return clean | ((q1 >= half) & (on_clean | on_noised))


def _block_of(pos, block: int):
    """``pos // block`` of positions that are never negative; a shift
    where the block is a power of two (no vector division on the chip)."""
    if block & (block - 1) == 0:
        return pos >> (block.bit_length() - 1)
    return pos // block


def resolve_flash_block(seq_len: int) -> int:
    """The tiling policy, shared by every flash call site: largest
    power-of-two divisor of the sequence length, capped at 1024.

    The cap is a VMEM-residency choice, not an MXU one: bigger tiles
    amortize the per-block online-softmax bookkeeping and k/v tile
    revisits, and a 1024² f32 score tile is 4 MiB. The backward holds
    several such tiles live at once, so every ``pallas_call`` here
    requests its VMEM explicitly (:func:`_compiler_params`, which says
    when the default limit is and is not enough). Throughput per tile
    size on a directly attached chip: not measured. Lengths whose
    power-of-two factor is below the sublane minimum (8) are rejected —
    they would tile into sub-MXU scalar-sized blocks, worse than einsum.

    ``TPUSNAPSHOT_FLASH_BLOCK_CAP`` lowers the cap without code changes
    for a generation with less VMEM."""
    import math

    from ..utils.env import env_int

    cap = env_int("TPUSNAPSHOT_FLASH_BLOCK_CAP", 1024)
    block = math.gcd(seq_len, cap)
    if block < 8:
        raise ValueError(
            f"flash attention needs a sequence length with a power-of-two "
            f"factor >= 8; {seq_len} tiles at {block} rows. Pad the "
            f"sequence or use the einsum path."
        )
    return block


def resolve_interpret() -> bool:
    """Whether the kernels run in Pallas interpreter mode: never on a
    TPU backend (they compile through Mosaic or the call fails), always
    on the CPU backend (the hermetic test suite). Any other backend is
    an error — interpreting there would hide that the kernels, written
    against the Mosaic lowering, never ran compiled."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"flash attention kernels target TPU (Mosaic); backend "
        f"{backend!r} is neither 'tpu' (compiled) nor 'cpu' "
        f"(interpreted for tests). Use the einsum path."
    )


_MIB = 1024 * 1024


def _compiler_params(block_q: int, block_k: int, d: int):
    """Scoped-VMEM request for one kernel launch.

    Live at once in the backward are s, p, dp, ds, the two causal
    position grids and their mask — [block_q, block_k] 4-byte tiles —
    beside the double-buffered q/k/v/dO row blocks and the lse/delta
    columns, whose trailing dim of 1 pads to 128 lanes: ~38 MiB at
    1024-row tiles. Observed on a v5e (libtpu 0.0.34): at the default
    matmul precision all three kernels fit Mosaic's default scoped limit
    at that tiling, but under ``jax.default_matmul_precision("highest")``
    (multi-pass float32 matmuls — how a float32 reference check must
    run) the backward is refused with ``RESOURCE_EXHAUSTED ... vmem``
    unless this request is made. Capped below the 128 MiB a TensorCore
    has."""
    tiles = 8 * 4 * block_q * block_k
    rows = 2 * 4 * (4 * max(block_q, block_k) * d + 2 * block_q * 128)
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(96 * _MIB, max(16 * _MIB, tiles + rows))
    )


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    window: Optional[int] = None,
    diffusion=None,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    last_k = pl.num_programs(2) - 1

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: key block kj is entirely in the future of query block qi
    # iff its first key index exceeds the last query index; under a
    # window it may also lie entirely behind every query's window.
    run = (
        _block_visible(qi, kj, block_q, block_k, window, diffusion)
        if causal else True
    )

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # [block_q, d]
        k = k_ref[0].astype(jnp.float32)  # [block_k, d]
        v = v_ref[0].astype(jnp.float32)  # [block_k, d]
        s = jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if causal:
            q_pos, k_pos = _causal_positions(qi, kj, block_q, block_k)
            s = jnp.where(_visible(q_pos, k_pos, window, diffusion), s, _NEG_INF)
        m_prev = m_ref[:]  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)  # [block_q, block_k]
        if causal and (window is not None or diffusion is not None):
            # The first block a query block visits need not hold a key
            # that each of its rows sees (causal alone: key 0 always
            # is; block diffusion: a noised query of the first block
            # sees no clean key). Such a row has m_new = -1e30 and
            # exp(s - m_new) = 1 on its masked keys, which must not
            # enter l or acc.
            p = jnp.where(_visible(q_pos, k_pos, window, diffusion), p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p,
            v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(kj == last_k)
    def _finish():
        # Fully-masked rows (can't happen with causal self-attention, but
        # keep the guard) would have l == 0; avoid 0/0.
        denom = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / denom).astype(o_ref.dtype)
        # Log-sum-exp per row, consumed by the backward kernels to
        # reconstruct p = exp(s - lse) without storing the score matrix.
        lse_ref[0] = m_ref[:] + jnp.log(denom)


def _bwd_pieces(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *, scale,
                causal, qi, kj, block_q, block_k, window=None, diffusion=None):
    """Recompute p and ds for one (q-block, k-block) pair — the shared
    core of both backward kernels. Returns (p, ds), both [block_q,
    block_k] float32."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    # All-masked rows (forward wrote lse = -1e30) must yield p = 0, not
    # exp(s + 1e30) = inf: clamp for the exp, then zero those rows.
    lse_raw = lse_ref[0]
    lse_safe = jnp.maximum(lse_raw, _NEG_INF / 2)
    p = jnp.where(lse_raw > _NEG_INF / 2, jnp.exp(s - lse_safe), 0.0)
    if causal:
        q_pos, k_pos = _causal_positions(qi, kj, block_q, block_k)
        p = jnp.where(_visible(q_pos, k_pos, window, diffusion), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [block_q, block_k]
    ds = p * (dp - delta_ref[0])
    return p, ds


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale, causal, block_q, block_k, window=None, diffusion=None,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (
        _block_visible(qi, kj, block_q, block_k, window, diffusion)
        if causal else True
    )

    @pl.when(run)
    def _step():
        _, ds = _bwd_pieces(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            scale=scale, causal=causal, qi=qi, kj=kj,
            block_q=block_q, block_k=block_k, window=window,
            diffusion=diffusion,
        )
        dq_acc[:] += scale * jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, scale, causal, block_q, block_k, window=None,
    diffusion=None,
):
    # Grid: (bh, n_k, n_q) — the q-block axis iterates sequentially so
    # the dk/dv accumulators persist across it.
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # Causal: q block strictly before the k block contributes nothing,
    # nor does one whose every query has left the block behind its window.
    run = (
        _block_visible(qi, kj, block_q, block_k, window, diffusion)
        if causal else True
    )

    @pl.when(run)
    def _step():
        p, ds = _bwd_pieces(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            scale=scale, causal=causal, qi=qi, kj=kj,
            block_q=block_q, block_k=block_k, window=window,
            diffusion=diffusion,
        )
        dv_acc[:] += jax.lax.dot_general(
            p, do_ref[0].astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # pᵀ·dO [block_k, d]
        dk_acc[:] += scale * jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # dsᵀ·q [block_k, d]

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _reference_attention(q, k, v, causal, window=None, block_diffusion=None):
    """Differentiable einsum attention — the kernels' numerical spec
    (forward and backward match it to float tolerance, not bitwise: the
    tiled kernels reassociate the softmax reductions). ``window``:
    query i sees the keys j with ``0 <= i - j < window``;
    ``block_diffusion``: :func:`flash_attention`'s."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / (d**0.5)
    if causal:
        length = q.shape[2]
        mask = jnp.tril(jnp.ones((length, length), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((length, length), bool), -window)
        if block_diffusion is not None:
            mask = jnp.asarray(block_diffusion_mask(*block_diffusion))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(
    q, k, v, causal, block_q, block_k, interpret, window, diffusion
):
    return _flash_forward(
        q, k, v, causal, block_q, block_k, interpret, window, diffusion
    )[0]


def _flash_fwd_rule(
    q, k, v, causal, block_q, block_k, interpret, window, diffusion
):
    out, lse = _flash_forward(
        q, k, v, causal, block_q, block_k, interpret, window, diffusion
    )
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(
    causal, block_q, block_k, interpret, window, diffusion, residuals, g
):
    # Tiled Pallas backward: p is reconstructed per tile from the saved
    # log-sum-exp, so the backward, like the forward, never materializes
    # the S×S score matrix (O(S·D) memory end to end). Two kernels: dq
    # accumulates over key blocks; dk/dv accumulate over query blocks.
    q, k, v, out, lse = residuals
    # delta_i = rowsum(dO_i · O_i) — the softmax-jacobian correction.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    dq, dk, dv = _flash_backward(
        q, k, v, g, lse, delta, causal, block_q, block_k, interpret, window,
        diffusion,
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _resolve_blocks(s: int, block_q: int, block_k: int):
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(
            f"sequence length {s} must be divisible by block sizes "
            f"({block_q}, {block_k})"
        )
    return block_q, block_k


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "interpret", "window", "diffusion"
    ),
)
def _flash_backward(
    q, k, v, g, lse, delta, causal, block_q, block_k, interpret, window=None,
    diffusion=None,
):
    b, h, s, d = q.shape
    group = _gqa_group(q, k)
    hkv = h // group
    block_q, block_k = _resolve_blocks(s, block_q, block_k)
    scale = 1.0 / (d**0.5)
    bh = b * h
    flat = lambda x: x.reshape(-1, s, x.shape[-1])  # noqa: E731
    qf, kf, vf, gf = flat(q), flat(k), flat(v), flat(g)
    lsef, deltaf = lse.reshape(bh, s, 1), delta.reshape(bh, s, 1)

    # Two index maps cover both grids: "block index is grid axis 1" vs
    # "grid axis 2". dq's grid is (bh, q, k); dk/dv's is (bh, k, q) — the
    # q-indexed operands ride axis 1 in the first and axis 2 in the
    # second, and vice versa for k-indexed ones. Under GQA the k-indexed
    # operands additionally collapse the q-head to its kv-head.
    by_axis1 = lambda bh_, a, b_: (bh_, a, 0)  # noqa: E731
    by_axis2 = lambda bh_, a, b_: (bh_, b_, 0)  # noqa: E731
    kv1 = _kv_index_map(h, group)  # k-operand indexed by grid axis 2
    row_q = pl.BlockSpec((1, block_q, d), by_axis1)
    row_k = pl.BlockSpec((1, block_k, d), kv1)
    aux_q = pl.BlockSpec((1, block_q, 1), by_axis1)

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, window=window,
            diffusion=diffusion,
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        grid=(bh, s // block_q, s // block_k),
        in_specs=[row_q, row_k, row_k, row_q, aux_q, aux_q],
        out_specs=row_q,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(block_q, block_k, d),
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    # dk/dv grid swaps the roles: k-block outer (axis 1), q-block inner.
    # Under GQA the kernel runs per Q-head (each contributes to its
    # kv-head's gradient); the per-q-head partials are group-summed after
    # the call — one transient [B,Hq,S,D] f32 pair, the same footprint as
    # the incoming cotangent, in exchange for unchanged kernel code.
    row_q2 = pl.BlockSpec((1, block_q, d), by_axis2)
    row_k2 = pl.BlockSpec((1, block_k, d), _kv_index_map(h, group, block_axis=1))
    out_k2 = pl.BlockSpec((1, block_k, d), by_axis1)
    aux_q2 = pl.BlockSpec((1, block_q, 1), by_axis2)
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, window=window,
            diffusion=diffusion,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, s, d), jnp.float32),
        ),
        grid=(bh, s // block_k, s // block_q),
        in_specs=[row_q2, row_k2, row_k2, row_q2, aux_q2, aux_q2],
        out_specs=(out_k2, out_k2),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(block_q, block_k, d),
        interpret=interpret,
    )(qf, kf, vf, gf, lsef, deltaf)

    dq = dq.reshape(b, h, s, d)
    if group == 1:
        return dq, dk.reshape(b, hkv, s, d), dv.reshape(b, hkv, s, d)
    dk = dk.reshape(b, hkv, group, s, d).sum(axis=2)
    dv = dv.reshape(b, hkv, group, s, d).sum(axis=2)
    return dq, dk, dv


def flash_attention(
    q: jax.Array,  # [B, H, S, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """softmax(QKᵀ/√D)·V without materializing the S×S score matrix.

    ``window`` (causal only): query i sees the keys j with ``0 <= i - j
    < window``; key blocks that lie wholly behind a query block's
    windows are skipped as those above the diagonal are. It need not be
    a multiple of the blocks. ``None`` is plain causal attention, and
    compiles to the program it compiled to before windows existed.

    ``block_diffusion = (half, block)``: the mask of block-diffusion
    training in place of the causal one. The sequence is ``half`` clean
    tokens and then their ``half`` noised copies, both in blocks of
    ``block`` tokens; what sees what is :func:`_diffusion_visible`'s
    (block-causal among the clean, block-diagonal among the noised,
    strictly block-causal from noised to clean). Tiles in which nothing
    is seen are skipped: about five eighths of them at four tiles a
    half, three quarters as the tiles get small. Neither ``half`` nor
    ``block`` need be a multiple of the tiles."""
    if block_diffusion is not None:
        half, block = (int(n) for n in block_diffusion)
        if not causal or window is not None:
            raise ValueError(
                "block_diffusion replaces the causal mask: causal stays "
                "True and there is no window"
            )
        if block < 1 or 2 * half != q.shape[2]:
            raise ValueError(
                f"block_diffusion=(half, block) needs a sequence of 2 * "
                f"half tokens and a block of at least 1; got "
                f"{(half, block)} for {q.shape[2]} tokens"
            )
        block_diffusion = (half, block)
    if window is not None:
        if not causal:
            raise ValueError("a window is a causal window: causal must be True")
        if window < 1:
            raise ValueError(f"window must be at least 1, got {window}")
        if window >= q.shape[2]:
            window = None  # every key at or before a query is within it
    if interpret is None:
        interpret = resolve_interpret()
    return _flash_attention(
        q, k, v, causal, block_q, block_k, interpret, window, block_diffusion
    )



def _gqa_group(q: jax.Array, k: jax.Array) -> int:
    """Query heads per key/value head. Dense attention is group 1;
    grouped-query attention (Hq = g·Hkv) maps q-head h to kv-head
    h // g — expressed in the kernels purely through BlockSpec index
    maps, so K/V are never materialized per q-head."""
    hq, hkv = q.shape[1], k.shape[1]
    if hq % hkv:
        raise ValueError(
            f"query heads ({hq}) must be a multiple of kv heads ({hkv})"
        )
    return hq // hkv


def _kv_index_map(h: int, group: int, block_axis: int = 2):
    """Flat q-head grid index -> flat kv-head row: bh = b·H + h_q maps to
    b·(H//group) + h_q//group. ``block_axis`` selects which grid axis
    carries the k-block index (2 for the forward/dq grids (bh, q, k),
    1 for the dk/dv grid (bh, k, q))."""
    hkv = h // group

    def index_map(bh, a, b_):
        return (
            (bh // h) * hkv + (bh % h) // group,
            a if block_axis == 1 else b_,
            0,
        )

    return index_map


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "block_q", "block_k", "interpret", "window", "diffusion"
    ),
)
def _flash_forward(
    q: jax.Array,  # [B, Hq, S, D]
    k: jax.Array,  # [B, Hkv, S, D] — Hq % Hkv == 0 (GQA); dense if equal
    v: jax.Array,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,  # resolved by flash_attention(); never None here
    window: Optional[int] = None,
    diffusion: Optional[Tuple[int, int]] = None,
):
    """Returns (out [B,Hq,S,D], lse [B,Hq,S,1] float32)."""
    b, h, s, d = q.shape
    group = _gqa_group(q, k)
    assert k.shape == v.shape == (b, h // group, s, d)
    block_q, block_k = _resolve_blocks(s, block_q, block_k)

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * (h // group), s, d)
    vf = v.reshape(b * (h // group), s, d)

    grid = (b * h, s // block_q, s // block_k)
    kernel = functools.partial(
        _flash_kernel,
        scale=1.0 / (d**0.5),
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        window=window,
        diffusion=diffusion,
    )
    kv_map = _kv_index_map(h, group)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(block_q, block_k, d),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s, 1)
