"""``models/lfm2_moe.py`` against the plain reference
(``perfbench/reference_lfm2.py``) at toy widths on the CPU, seeded
random weights.

The program is run in float32 here (``dtype=float32``), so what it is
compared with is the same arithmetic in another order: a fused gate and
up projection against two products, batched experts against a loop, the
convolution's taps in another order, rotary on halves against
``rotate_half``, the tied head as an einsum against a transpose. The
tolerances are therefore those of float32 reassociation, and each is
tight enough that the same program computing in bfloat16 fails it
(``test_a_bfloat16_pass_fails_the_same_tolerances``).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import reference_lfm2 as ref  # noqa: E402
from torchsnapshot_tpu.models import experts  # noqa: E402
from torchsnapshot_tpu.models import lfm2_moe as lfm2  # noqa: E402

# Two dense layers, then attention and a short convolution with experts:
# every kind of layer the cell holds.
TOY = lfm2.Lfm2MoeConfig(
    hidden_size=32,
    layer_types=("conv", "conv", "full_attention", "conv"),
    num_dense_layers=2,
    vocab_size=64,
    num_attention_heads=4,
    num_key_value_heads=2,
    rope_theta=100.0,
    flash_attention=False,
    intermediate_size=48,
    num_experts=32,
    expert_ids=(4, 5, 6, 7, 8, 9, 10, 11),
    num_experts_per_tok=4,
    moe_intermediate_size=16,
    dtype=jnp.float32,
)
SEQ = 20


def ref_config(config: lfm2.Lfm2MoeConfig) -> dict:
    """The reference's plain dict, spelled with the published keys."""
    doc = dataclasses.asdict(config)
    doc["layer_types"] = list(config.layer_types)
    doc["layers_held"] = len(config.layer_types)
    doc["expert_ids"] = list(config.expert_ids)
    return doc


def toy_params(config=TOY, seed=0, scale=8.0):
    """Weights large enough (0.16 a matrix entry) that every part of a
    layer moves the residual stream, and norm weights that are not 1
    (0.7 to 1.3), the query/key norms' among them: an error in one part
    is not hidden by the others."""
    master = lfm2.init_master(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 64))
    master = jax.tree.map(
        lambda w: w * scale if w.ndim >= 2
        else w + jax.random.uniform(next(keys), w.shape, minval=-0.3, maxval=0.3),
        master,
    )
    return jax.tree.map(lambda w: w.astype(config.dtype), master)


def toy_bias(config=TOY, seed=7):
    """Biases of the size the update gives after a few dozen steps: they
    move the choice of some tokens, not of most."""
    return [
        jax.random.uniform(jax.random.key(seed + i), (config.num_experts,),
                           minval=-0.05, maxval=0.05)
        for i, _ in enumerate(config.sparse_layers)
    ]


def toy_tokens(config=TOY, batch=2, seq=SEQ, seed=1):
    return lfm2.draw_tokens(jax.random.key(seed), (batch, seq), config)


def worst(got, want):
    """Largest error of a leaf as a share of the leaf's largest entry."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


# Float32 reassociation: sums of up to a few hundred products in another
# order move a result by a few units of 2**-24 = 6e-8 relative to its
# largest term, and the errors add through four layers and the backward
# pass. Measured here: 3e-6 at worst. A bfloat16 pass (8 bits of
# mantissa, 4e-3 a rounding) reads 1e-3 to 3e-2 a leaf.
REASSOCIATION = 2e-5


def _grad_errors(config, bias=None):
    params, tokens = toy_params(config), toy_tokens(config)
    bias = toy_bias(config) if bias is None else bias
    (loss, loads), grads = jax.value_and_grad(lfm2.loss_fn, has_aux=True)(
        params, bias, tokens, config
    )
    (want_loss, want_loads), want_grads = jax.value_and_grad(ref.loss, has_aux=True)(
        jax.tree.map(lambda p: p.astype(jnp.float32), params), bias, tokens,
        ref_config(config),
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(worst, grads, want_grads)
    )
    errors = {jax.tree_util.keystr(path): err for path, err in flat}
    loads_equal = all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(loads, want_loads)
    )
    return abs(float(loss) - float(want_loss)) / float(want_loss), errors, loads_equal


def test_logits_match_the_reference():
    params, tokens, bias = toy_params(), toy_tokens(), toy_bias()
    got = lfm2.forward(params, bias, tokens, TOY)
    want, _ = ref.forward(params, bias, tokens, ref_config(TOY))
    assert got.shape == (2, SEQ, TOY.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.std(want)) > 0.1  # the comparison is not of noise around 0
    assert worst(got, want) < REASSOCIATION
    # causal: what follows a token does not reach its logits
    later = tokens.at[:, SEQ // 2 :].set((tokens[:, SEQ // 2 :] + 1) % TOY.vocab_size)
    np.testing.assert_array_equal(
        np.asarray(lfm2.forward(params, bias, later, TOY)[:, : SEQ // 2]),
        np.asarray(got[:, : SEQ // 2]),
    )


def test_loss_every_gradient_leaf_and_the_loads_match_the_reference():
    loss_error, errors, loads_equal = _grad_errors(TOY)
    assert loss_error < REASSOCIATION
    # embedding, final norm; two dense conv layers of 7 leaves, the
    # attention layer of 11 and the sparse conv layer of 8
    assert len(errors) == 2 + 2 * 7 + 11 + 8
    assert max(errors.values()) < REASSOCIATION, max(errors, key=errors.get)
    assert loads_equal


def test_a_bfloat16_pass_fails_the_same_tolerances():
    """The tolerances would catch a program that computes in a lower
    precision than its configuration states."""
    loss_error, errors, _ = _grad_errors(dataclasses.replace(TOY, dtype=jnp.bfloat16))
    failing = [name for name, err in errors.items() if err > REASSOCIATION]
    assert loss_error > REASSOCIATION
    assert len(failing) > len(errors) // 2


def test_remat_changes_nothing():
    params, tokens, bias = toy_params(), toy_tokens(), toy_bias()
    plain = dataclasses.replace(TOY, remat=False)
    a, _ = jax.grad(lfm2.loss_fn, has_aux=True)(params, bias, tokens, TOY)
    b, _ = jax.grad(lfm2.loss_fn, has_aux=True)(params, bias, tokens, plain)
    assert max(jax.tree.leaves(jax.tree.map(worst, a, b))) < 1e-6


# ------------------------------------------------------------- operators


def test_the_short_convolution_against_a_hand_written_numpy_one():
    """``[B, C, X]`` thirds of ``h W_in`` in that order, ``u = B X``, three
    causal taps without bias, ``(C v) W_out``, token by token."""
    blk = toy_params()["layers"][0]
    h = jax.random.normal(jax.random.key(3), (2, 9, TOY.hidden_size))
    got = lfm2.short_conv(h, blk)
    w = {k: np.asarray(v, np.float64) for k, v in blk.items()}
    proj = np.asarray(h, np.float64) @ w["in_proj"]
    d = TOY.hidden_size
    b, c, x = proj[..., :d], proj[..., d : 2 * d], proj[..., 2 * d :]
    u = b * x
    v = np.zeros_like(u)
    for t in range(9):
        for j in range(3):  # tap j reads u at t - 2 + j
            if t - 2 + j >= 0:
                v[:, t] += w["conv_w"][j] * u[:, t - 2 + j]
    want = (c * v) @ w["out_proj"]
    assert worst(got, want) < REASSOCIATION
    with jax.default_matmul_precision("highest"):
        theirs = ref.short_conv(h, blk, ref_config(TOY))
    assert worst(theirs, want) < REASSOCIATION
    # no bias: a zero input gives a zero output
    assert not np.asarray(lfm2.short_conv(jnp.zeros_like(h), blk)).any()


@pytest.mark.parametrize("flash", [False, True], ids=["einsum", "flash"])
def test_attention_against_the_reference(flash, monkeypatch):
    """Causal grouped-query attention (4 query heads over 2, head width
    hidden / heads = 8, query/key norms, rotary) through the einsum form
    and through ``ops/attention.py`` (interpreted, 8-row tiles)."""
    monkeypatch.setenv("TPUSNAPSHOT_FLASH_BLOCK_CAP", "8")
    config = dataclasses.replace(TOY, flash_attention=flash)
    assert config.head_dim == 8
    blk = toy_params()["layers"][2]
    h = jax.random.normal(jax.random.key(9), (2, 24, TOY.hidden_size))
    got = lfm2.attention(h, blk, config)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(h, blk, ref_config(config), query_block=8)
    assert float(jnp.std(want)) > 0.1
    assert worst(got, want) < REASSOCIATION


# -------------------------------------------------------------- experts


def test_the_bias_enters_the_choice_alone():
    """``s = sigmoid(h Wr)``; the top k of ``s + b``; their ``s`` divided
    by their sum. A bias that moves the choice moves which experts get a
    weight, never the weight a chosen expert gets from its score."""
    blk = toy_params()["layers"][3]
    x = jax.random.normal(jax.random.key(5), (48, TOY.hidden_size))
    bias = 0.3 * jax.random.normal(jax.random.key(6), (TOY.num_experts,))
    gates, routed = experts.held_gates(x, blk["router"], bias, TOY.routing)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(blk["router"], np.float64))))
    want = np.zeros((48, len(TOY.expert_ids)))
    load = np.zeros(TOY.num_experts, int)
    for token in range(48):
        top = np.argsort(-(s[token] + np.asarray(bias)))[:4]
        load[top] += 1
        for e in top:
            if e in TOY.expert_ids:
                want[token, TOY.expert_ids.index(e)] = s[token, e] / s[token, top].sum()
    np.testing.assert_allclose(np.asarray(gates), want, rtol=2e-5, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(routed), want > 0)
    np.testing.assert_array_equal(
        np.asarray(experts.expert_loads(x, blk["router"], bias, TOY.routing)), load
    )
    assert load.sum() == 48 * 4
    unbiased, _ = experts.held_gates(x, blk["router"], None, TOY.routing)
    assert bool(jnp.any((unbiased > 0) != (gates > 0)))


def test_expert_shares_add_up_to_the_uncut_layer():
    """All 4 shares of an expert layer (8 of 32 experts each; there is no
    shared expert to count once) give the reference's uncut layer, and
    every share counts the same loads, so moves the bias alike: the share
    is a cut of the model, not another model."""
    blk = toy_params()["layers"][3]
    bias = toy_bias()[0]
    h = jax.random.normal(jax.random.key(5), (2, 24, TOY.hidden_size))
    flat = h.reshape(-1, TOY.hidden_size)
    f, d = TOY.moe_intermediate_size, TOY.hidden_size
    gate_up = 0.16 * jax.random.normal(jax.random.key(6), (32, d, 2 * f))
    down = 0.16 * jax.random.normal(jax.random.key(7), (32, f, d))
    whole = dict(blk, gate_up=gate_up, down=down)
    with jax.default_matmul_precision("highest"):
        uncut, uncut_loads = ref.routed_experts(
            flat, whole, bias, ref_config(TOY), list(range(32))
        )
    total = jnp.zeros_like(h)
    updated = []
    for share in range(4):
        ids = tuple(range(8 * share, 8 * share + 8))
        held = dict(whole, gate_up=gate_up[jnp.array(ids)], down=down[jnp.array(ids)])
        config = dataclasses.replace(TOY, expert_ids=ids)
        part, loads = lfm2.sparse_mlp(h, held, bias, config)
        with jax.default_matmul_precision("highest"):
            want, _ = ref.routed_experts(flat, held, bias, ref_config(config), list(ids))
        assert worst(part.reshape(flat.shape), want) < REASSOCIATION
        np.testing.assert_array_equal(np.asarray(loads), np.asarray(uncut_loads))
        updated.append(lfm2.update_expert_bias([bias], [loads], TOY.expert_bias_rate)[0])
        total = total + part
    assert float(jnp.max(jnp.abs(uncut))) > 0.1
    assert worst(total.reshape(flat.shape), uncut) < REASSOCIATION
    for other in updated[1:]:
        np.testing.assert_array_equal(np.asarray(other), np.asarray(updated[0]))


# ---------------------------------------------------------------- state


def test_state_is_sixteen_bytes_a_parameter_with_gradients_and_the_biases():
    config = dataclasses.replace(TOY, dtype=jnp.bfloat16)
    state = jax.eval_shape(lambda k: lfm2.init_state(config, k), jax.random.key(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(state["master"]))
    saved = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(state)
    )
    assert saved == 14 * n_params + 4 + 2 * 32 * 4  # the count, two biases of 32
    assert len(jax.tree.leaves(state)) == 4 * 35 + 1 + 2
    assert sorted(state) == ["master", "opt", "params"]
    weights, biases = lfm2.split_biases(state["params"])
    assert [(b.shape, b.dtype) for b in biases] == [((32,), jnp.float32)] * 2
    assert "expert_bias" not in state["master"]  # no optimizer moves them
    blk = state["params"]["layers"]
    assert blk[0]["in_proj"].shape == (32, 96) and blk[0]["conv_w"].shape == (3, 32)
    assert blk[0]["gate_up"].shape == (32, 96) and blk[0]["down"].shape == (48, 32)
    assert blk[2]["wq"].shape == (32, 32) and blk[2]["wk"].shape == (32, 16)
    assert blk[2]["q_norm"].shape == blk[2]["k_norm"].shape == (8,)
    assert blk[3]["gate_up"].shape == (8, 32, 32) and blk[3]["down"].shape == (8, 16, 32)
    assert blk[3]["router"].shape == (32, 32)  # the router keeps its published width
    assert "head" not in state["params"]  # tied
    grads = jax.eval_shape(
        lambda p, b, t: jax.grad(lfm2.loss_fn, has_aux=True)(p, b, t, config)[0],
        weights, biases, toy_tokens(),
    )
    assert saved - 4 - 256 + sum(
        int(np.prod(g.shape)) * g.dtype.itemsize for g in jax.tree.leaves(grads)
    ) == 16 * n_params


def test_one_step_moves_every_part_of_the_state_and_the_biases_by_the_loads():
    config = dataclasses.replace(TOY, dtype=jnp.bfloat16)
    hp = lfm2.AdamW(lr=1e-2)
    state = lfm2.init_state(config, jax.random.key(2))
    tokens = toy_tokens(config)
    stepped, loss = jax.jit(
        lambda s, t: lfm2.adamw_train_step(s, t, None, config, hp)
    )(state, tokens)
    assert np.isfinite(float(loss)) and int(stepped["opt"][1]) == 1
    assert jax.tree.structure(stepped) == jax.tree.structure(state)
    moved = jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)), state["master"], stepped["master"]
    )
    assert all(jax.tree.leaves(moved)), moved
    # the biases: the reference's update of the loads the step's own
    # forward pass counted, on the parameters before the update
    weights, biases = lfm2.split_biases(state["params"])
    _, loads = lfm2.loss_fn(weights, biases, tokens, config)
    want = ref.update_bias(biases, loads, 0.001)
    for got, expected, load in zip(stepped["params"]["expert_bias"], want, loads):
        np.testing.assert_array_equal(np.asarray(got), expected)
        assert set(np.unique(np.asarray(got)).tolist()) <= {-0.001, 0.0, 0.001} | {
            float(np.float32(0.001)), float(np.float32(-0.001))
        }
        assert int(np.sum(load)) == 2 * SEQ * 4
    assert any(bool(jnp.any(b != 0)) for b in stepped["params"]["expert_bias"])


def test_a_configuration_file_is_read_as_published():
    """The published keys of the cell's file give the program's
    configuration; the layers held are the first of ``layer_types``."""
    import json

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench/configs/lfm2-8b-a1b-ep4.json")) as f:
        config = json.load(f)
    cfg = lfm2.config_from_published(config)
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv", "conv", "conv")
    assert cfg.head_dim == 64 and cfg.sparse_layers == [2, 3, 4, 5]
    assert cfg.num_experts == 32 and len(cfg.expert_ids) == 8
    assert cfg.routing.scoring == "sigmoid" and cfg.routing.normalise
    for change, match in (
        ({"conv_bias": True}, "without bias"),
        ({"use_expert_bias": False}, "expert bias"),
        ({"tie_word_embeddings": False}, "ties the head"),
        ({"num_experts": 5}, "one id each"),
        ({"layer_types": ["sliding_attention"] * 24}, "runs conv"),
    ):
        with pytest.raises(ValueError, match=match):
            lfm2.config_from_published(dict(config, **change))
