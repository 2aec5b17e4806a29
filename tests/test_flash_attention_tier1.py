"""The attention kernel three benchmark cells run, held to its einsum
reference in tier-1: ``tests/test_flash_attention.py`` is wholly ``slow``
(larger shapes and grids), so these are its smallest shapes, a 2 x 2 grid
of blocks each, run by the Pallas interpreter in a second or two."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchsnapshot_tpu.ops.attention import (
    _reference_attention,
    flash_attention,
)

_HEADS = {"multi_head": (4, 4), "grouped_query": (4, 2)}


def _qkv(heads, seed):
    hq, hkv = _HEADS[heads]
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (
        jax.random.normal(kq, (1, hq, 64, 16), jnp.float32),
        jax.random.normal(kk, (1, hkv, 64, 16), jnp.float32),
        jax.random.normal(kv, (1, hkv, 64, 16), jnp.float32),
    )


def _flash(q, k, v, causal):
    return flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)


def _reference(q, k, v, causal):
    # Grouped-query attention is dense attention with each kv head
    # repeated for its group of query heads.
    group = q.shape[1] // k.shape[1]
    return _reference_attention(
        q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1), causal
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("heads", list(_HEADS))
def test_forward_matches_reference(heads, causal):
    q, k, v = _qkv(heads, seed=3)
    np.testing.assert_allclose(
        np.asarray(_flash(q, k, v, causal)),
        np.asarray(_reference(q, k, v, causal)),
        atol=2e-5,
        rtol=1e-5,
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("heads", list(_HEADS))
def test_gradients_match_reference(heads, causal):
    q, k, v = _qkv(heads, seed=5)

    def grads(attention):
        return jax.grad(
            lambda q, k, v: jnp.sum(attention(q, k, v, causal) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)

    for got, want in zip(grads(_flash), grads(_reference)):
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-4
        )


@pytest.mark.parametrize(
    "q_shape,kv_shape,match",
    [
        ((1, 1, 48, 16), (1, 1, 48, 16), "divisible"),
        ((1, 6, 32, 16), (1, 4, 32, 16), "multiple of kv heads"),
    ],
    ids=["sequence", "heads"],
)
def test_indivisible_shapes_rejected(q_shape, kv_shape, match):
    q, k = jnp.zeros(q_shape), jnp.zeros(kv_shape)
    with pytest.raises(ValueError, match=match):
        flash_attention(q, k, k, causal=True, block_q=32, block_k=32)


def test_transformer_flash_forward_equals_einsum_forward():
    from torchsnapshot_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
    )

    kw = dict(
        vocab_size=64, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=32,
    )
    einsum = TransformerConfig(**kw)
    params = init_params(einsum, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)
    np.testing.assert_allclose(
        np.asarray(
            forward(params, tokens, TransformerConfig(**kw, flash_attention=True))
        ),
        np.asarray(forward(params, tokens, einsum)),
        atol=2e-4,
        rtol=1e-4,
    )
