"""The block-diffusion mask of ``ops/attention.py`` (a sequence of two
halves, the clean tokens and then their noised copies, in blocks: a
clean query sees the clean keys of its own block and the blocks before;
a noised query the clean keys of the blocks strictly before its own and
the noised keys of its own block), held to the einsum reference at
tier-1 sizes: forward and both backward kernels, blocks of one token, of
a few, of no power of two and of the whole half, a half that is no
multiple of the tile (so a tile straddles the halves), the tile skip
against the mask, and the causal and window programs, which have to
lower to what they lowered to before this mask existed.

Grids of 16-row tiles, run by the Pallas interpreter."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchsnapshot_tpu.ops.attention import (
    _block_visible,
    block_diffusion_mask,
    _reference_attention,
    flash_attention,
)

TILE = 16
# (half, block): blocks of 1, 4 and the half at a half of two tiles; a
# block that is no power of two; a half of a tile and a half (24), so
# that the second tile holds clean and noised tokens; below a tile.
CASES = [(32, 1), (32, 4), (32, 32), (24, 3), (24, 4), (8, 4), (40, 8)]


def token_by_token(half, block):
    """The mask from (half, block) of each token, written out."""
    mask = np.zeros((2 * half, 2 * half), bool)
    for i in range(2 * half):
        for j in range(2 * half):
            i_noised, j_noised = i >= half, j >= half
            bi, bj = (i % half) // block, (j % half) // block
            if i_noised and j_noised:
                mask[i, j] = bi == bj
            elif i_noised and not j_noised:
                mask[i, j] = bj < bi
            elif not i_noised and not j_noised:
                mask[i, j] = bj <= bi
    return mask


def _qkv(seed, half, hq=4, hkv=2):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    shape = lambda h: (1, h, 2 * half, 16)
    return (
        jax.random.normal(kq, shape(hq), jnp.float32),
        jax.random.normal(kk, shape(hkv), jnp.float32),
        jax.random.normal(kv, shape(hkv), jnp.float32),
    )


def _flash(q, k, v, case, block_q=None, block_k=None):
    tile = TILE if q.shape[2] > TILE else 8  # two tiles a side at least
    return flash_attention(
        q, k, v, block_q=block_q or tile, block_k=block_k or tile,
        block_diffusion=case,
    )


def _reference(q, k, v, case):
    group = q.shape[1] // k.shape[1]
    return _reference_attention(
        q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), True,
        block_diffusion=case,
    )


@pytest.mark.parametrize("case", CASES, ids=str)
def test_the_mask_is_the_one_built_token_by_token(case):
    half, block = case
    np.testing.assert_array_equal(
        block_diffusion_mask(half, block), token_by_token(half, block)
    )
    mask = token_by_token(half, block)
    assert mask.any(axis=1).all()  # every row sees at least its own block
    assert not mask[:half, half:].any()  # no clean query sees a noised key
    # and the reference's attention is the one under that mask
    q, k, v = _qkv(seed=1, half=half, hkv=4)
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) / 4.0
    s = np.where(mask, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), np.asarray(v))
    np.testing.assert_allclose(
        np.asarray(_reference_attention(q, k, v, True, block_diffusion=case)), want,
        atol=2e-5, rtol=1e-5,
    )


@pytest.mark.parametrize("case", CASES, ids=str)
def test_forward_matches_reference(case):
    q, k, v = _qkv(seed=3, half=case[0])
    np.testing.assert_allclose(
        np.asarray(_flash(q, k, v, case)), np.asarray(_reference(q, k, v, case)),
        atol=2e-5, rtol=1e-5,
    )


@pytest.mark.parametrize("case", CASES, ids=str)
def test_both_gradient_kernels_match_reference(case):
    q, k, v = _qkv(seed=5, half=case[0])

    def grads(attention):
        return jax.grad(
            lambda q, k, v: jnp.sum(attention(q, k, v, case) ** 2), argnums=(0, 1, 2)
        )(q, k, v)

    for got, want in zip(grads(_flash), grads(_reference)):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-4
        )


@pytest.mark.parametrize("blocks", [(32, 16), (16, 32)])
def test_uneven_tiles(blocks):
    q, k, v = _qkv(seed=7, half=32)
    np.testing.assert_allclose(
        np.asarray(_flash(q, k, v, (32, 4), *blocks)),
        np.asarray(_reference(q, k, v, (32, 4))),
        atol=2e-5, rtol=1e-5,
    )


def test_tiles_in_which_nothing_is_seen_are_skipped_and_no_other():
    """``_block_visible`` against the mask: a tile runs iff one of its
    (query, key) pairs is visible; with the clean half first about five
    eighths of the tiles are skipped at four tiles a half."""
    for half, block in CASES + [(64, 4)]:
        mask = token_by_token(half, block)
        for bq, bk in ((16, 16), (32, 16), (16, 32), (8, 8)):
            if (2 * half) % bq or (2 * half) % bk:
                continue
            ran = 0
            for qi in range(2 * half // bq):
                for kj in range(2 * half // bk):
                    seen = mask[qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk].any()
                    got = bool(_block_visible(qi, kj, bq, bk, None, (half, block)))
                    assert got == seen, (half, block, bq, bk, qi, kj)
                    ran += got
            if (half, block, bq, bk) == (64, 4, 16, 16):
                assert ran == 24  # of 64: 10 clean-clean, 10 noised-clean, 4 own


@pytest.mark.parametrize(
    "kwargs,match",
    [
        ({"causal": False, "block_diffusion": (32, 4)}, "replaces the causal mask"),
        ({"window": 8, "block_diffusion": (32, 4)}, "no window"),
        ({"block_diffusion": (16, 4)}, "2 \\* half tokens"),
        ({"block_diffusion": (32, 0)}, "at least 1"),
    ],
)
def test_a_mask_that_means_nothing_is_refused(kwargs, match):
    q, k, v = _qkv(seed=11, half=32)
    with pytest.raises(ValueError, match=match):
        flash_attention(q, k, v, block_q=TILE, block_k=TILE, **kwargs)


# What the causal and the window kernels lowered to at PR 35 (fe93898),
# the parent of the PR that brought this mask: sha256 of
# ``jax.jit(...).lower(...).as_text()`` on the CPU backend (interpreted
# kernels, so the whole kernel body is in the text), jax/jaxlib 0.9.0,
# computed on a ``git archive`` of that commit. The causal pair is also
# what ``test_flash_attention_window.py`` pins from PR 32. Six cells'
# steps run these programs; a third mask must not have changed one
# operation of them.
LOWERING_AT_PR_35 = {
    ("causal", "forward"): (43839, "f50dfc8ec16dc937b2aab01a2842204c018854b00bd8a5faefb59184913c74d6"),
    ("causal", "gradients"): (154415, "1b1aa3c548921f50ac58432281b3c168835b2e5dd606fdb8ceb5be6e4fac9d77"),
    ("window", "forward"): (45706, "e0e874062bb666f9f88a0aecb8321d99a3415eaa819792e7bf934004bf1f09de"),
    ("window", "gradients"): (158568, "7e376ecfef5c24b406c3a24868f6e9ef7103b4c022e88c41bf05fd901874e3e1"),
}


@pytest.mark.parametrize("mask,which", sorted(LOWERING_AT_PR_35))
def test_causal_and_window_programs_lower_to_what_they_did_before(mask, which):
    if jax.__version__ != "0.9.0":
        pytest.skip("the pinned text is jax 0.9.0's")
    q = jax.ShapeDtypeStruct((1, 4, 64, 16), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 2, 64, 16), jnp.float32)
    window = 24 if mask == "window" else None

    def forward(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32, window=window
        )

    def gradients(q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.sum(forward(q, k, v) ** 2), argnums=(0, 1, 2)
        )(q, k, v)

    text = jax.jit({"forward": forward, "gradients": gradients}[which]).lower(
        q, k, k
    ).as_text()
    length, digest = LOWERING_AT_PR_35[(mask, which)]
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == (length, digest)
