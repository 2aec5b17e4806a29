"""The causal window of ``ops/attention.py`` (query i sees the keys j
with ``0 <= i - j < window``), held to the einsum reference at tier-1
sizes: forward and both backward kernels, windows below, at and above
the block, one that is no multiple of it, one at least the sequence
(plain causal), and the causal programs themselves, which have to lower
to what they lowered to before windows existed.

A 4 x 4 grid of 16-row blocks each, run by the Pallas interpreter."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchsnapshot_tpu.ops.attention import (
    _block_visible,
    _reference_attention,
    flash_attention,
)

SEQ, BLOCK = 64, 16
# below the block; no multiple of it; the block; a block and a half; one
# short of the sequence
WINDOWS = [1, 5, 16, 24, 63]


def _qkv(seed, hq=4, hkv=2, seq=SEQ):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (
        jax.random.normal(kq, (1, hq, seq, 16), jnp.float32),
        jax.random.normal(kk, (1, hkv, seq, 16), jnp.float32),
        jax.random.normal(kv, (1, hkv, seq, 16), jnp.float32),
    )


def _flash(q, k, v, window, block_q=BLOCK, block_k=BLOCK):
    return flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k, window=window
    )


def _reference(q, k, v, window):
    group = q.shape[1] // k.shape[1]
    return _reference_attention(
        q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), True, window
    )


def test_the_reference_mask_is_the_one_built_from_positions():
    """``_reference_attention``'s window against a mask written out from
    positions, on scores that tell every key apart."""
    q, k, v = _qkv(seed=1, hkv=4)
    for window in WINDOWS:
        i, j = np.arange(SEQ)[:, None], np.arange(SEQ)[None, :]
        mask = (j <= i) & (i - j < window)
        s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / 4.0
        s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), np.asarray(v))
        np.testing.assert_allclose(
            np.asarray(_reference_attention(q, k, v, True, window)), want,
            atol=2e-5, rtol=1e-5,
        )


@pytest.mark.parametrize("window", WINDOWS)
def test_forward_matches_reference(window):
    q, k, v = _qkv(seed=3)
    np.testing.assert_allclose(
        np.asarray(_flash(q, k, v, window)),
        np.asarray(_reference(q, k, v, window)),
        atol=2e-5,
        rtol=1e-5,
    )


@pytest.mark.parametrize("window", WINDOWS)
def test_both_gradient_kernels_match_reference(window):
    q, k, v = _qkv(seed=5)

    def grads(attention):
        return jax.grad(
            lambda q, k, v: jnp.sum(attention(q, k, v, window) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)

    for got, want in zip(grads(_flash), grads(_reference)):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-4
        )


@pytest.mark.parametrize("blocks", [(32, 16), (16, 32)])
def test_uneven_blocks_under_a_window(blocks):
    q, k, v = _qkv(seed=7)
    np.testing.assert_allclose(
        np.asarray(_flash(q, k, v, 24, *blocks)),
        np.asarray(_reference(q, k, v, 24)),
        atol=2e-5,
        rtol=1e-5,
    )


@pytest.mark.parametrize("window", [SEQ, SEQ + 1, 10 * SEQ])
def test_a_window_of_the_sequence_or_more_is_plain_causal(window):
    q, k, v = _qkv(seed=9)
    np.testing.assert_array_equal(
        np.asarray(_flash(q, k, v, window)), np.asarray(_flash(q, k, v, None))
    )


def test_blocks_behind_every_window_are_skipped_and_no_other():
    """``_block_visible`` against the mask: a block runs iff one of its
    (query, key) pairs is visible."""
    for window in WINDOWS + [None]:
        for bq, bk in ((16, 16), (32, 16), (16, 32)):
            for qi in range(SEQ // bq):
                for kj in range(SEQ // bk):
                    i = np.arange(qi * bq, (qi + 1) * bq)[:, None]
                    j = np.arange(kj * bk, (kj + 1) * bk)[None, :]
                    seen = j <= i
                    if window is not None:
                        seen &= i - j < window
                    assert bool(_block_visible(qi, kj, bq, bk, window)) == seen.any()


@pytest.mark.parametrize(
    "kwargs,match",
    [
        ({"causal": False, "window": 8}, "causal window"),
        ({"causal": True, "window": 0}, "at least 1"),
    ],
)
def test_a_window_that_means_nothing_is_refused(kwargs, match):
    q, k, v = _qkv(seed=11)
    with pytest.raises(ValueError, match=match):
        flash_attention(q, k, v, block_q=BLOCK, block_k=BLOCK, **kwargs)


# What the causal kernels lowered to at PR 32 (c3c49d0), the parent of
# the PR that brought windows: sha256 of ``jax.jit(...).lower(...)
# .as_text()`` on the CPU backend (interpreted kernels, so the whole
# kernel body is in the text; the functions named as below, since the
# text holds their names), jax/jaxlib 0.9.0. Five cells' steps run
# these programs; a window must not have changed one operation of them.
CAUSAL_LOWERING_AT_PR_32 = {
    "forward": (43839, "f50dfc8ec16dc937b2aab01a2842204c018854b00bd8a5faefb59184913c74d6"),
    "gradients": (154415, "1b1aa3c548921f50ac58432281b3c168835b2e5dd606fdb8ceb5be6e4fac9d77"),
}


@pytest.mark.parametrize("which", sorted(CAUSAL_LOWERING_AT_PR_32))
def test_causal_programs_lower_to_what_they_did_before_windows(which):
    if jax.__version__ != "0.9.0":
        pytest.skip("the pinned text is jax 0.9.0's")
    q = jax.ShapeDtypeStruct((1, 4, 64, 16), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2, 64, 16), jnp.float32)

    def forward(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32)

    def gradients(q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.sum(forward(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)

    text = jax.jit({"forward": forward, "gradients": gradients}[which]).lower(
        q, k, k
    ).as_text()
    length, digest = CAUSAL_LOWERING_AT_PR_32[which]
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == (length, digest)
