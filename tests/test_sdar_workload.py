"""``models/sdar.py`` against the plain reference
(``perfbench/reference_sdar.py``) at toy widths on the CPU, seeded
random weights.

The program is run in float32 here (``dtype=float32``), so what it is
compared with is the same arithmetic in another order: a fused gate and
up projection against two products, gathered or batched experts against
a loop, rotary on halves against ``rotate_half``, the kernel's tiled
softmax against materialised scores, a head on the noised half against
one on both. Tolerances are therefore those of float32 reassociation,
and each is tight enough that the same program computing in bfloat16
fails it (``test_a_bfloat16_pass_fails...``).
"""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import reference_sdar as ref  # noqa: E402
from torchsnapshot_tpu.models import experts  # noqa: E402
from torchsnapshot_tpu.models import sdar  # noqa: E402
from torchsnapshot_tpu.ops.attention import block_diffusion_mask  # noqa: E402

TOY = sdar.SdarConfig(
    hidden_size=32,
    layers=3,
    vocab_size=64,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    rope_theta=100.0,
    flash_attention=False,
    num_experts=32,
    expert_ids=(4, 5, 6, 7),
    num_experts_per_tok=3,
    moe_intermediate_size=16,
    block_length=4,
    dtype=jnp.float32,
)
SEQ = 20  # five blocks; 2 x 20 tokens are no multiple of 16
SHORT = dataclasses.replace(TOY, layers=2)
NOISE_KEY = jax.random.key(17)


def ref_config(config: sdar.SdarConfig) -> dict:
    """The reference's plain dict, spelled with the published keys."""
    doc = dataclasses.asdict(config)
    doc["expert_ids"] = list(config.expert_ids)
    return doc


def toy_params(config=TOY, seed=0, scale=8.0):
    """Weights large enough (0.16 a matrix entry) that every part of a
    layer moves the residual stream, and norm weights that are not 1
    (0.7 to 1.3), the query/key norms' among them: an error in one part
    is not hidden by the others."""
    master = sdar.init_master(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 64))
    master = jax.tree.map(
        lambda w: w * scale if w.ndim >= 2
        else w + jax.random.uniform(next(keys), w.shape, minval=-0.3, maxval=0.3),
        master,
    )
    return jax.tree.map(lambda w: w.astype(config.dtype), master)


def toy_tokens(config=TOY, batch=2, seq=SEQ, seed=1):
    return sdar.draw_tokens(jax.random.key(seed), (batch, seq), config)


def worst(got, want):
    """Largest error of a leaf as a share of the leaf's largest entry."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


# Float32 reassociation: sums of up to a few hundred products in another
# order move a result by a few units of 2**-24 = 6e-8 relative to its
# largest term, and the errors add through three layers and the backward
# pass. Measured here: 1e-6 at worst. A bfloat16 pass (8 bits of
# mantissa, 4e-3 a rounding) reads 3e-3 to 3e-2 a leaf, 2.7e-4 the loss.
REASSOCIATION = 2e-5


def _grad_errors(config):
    params, tokens = toy_params(config), toy_tokens(config)
    loss, grads = jax.value_and_grad(sdar.loss_fn)(params, tokens, NOISE_KEY, config)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(
        jax.tree.map(lambda p: p.astype(jnp.float32), params), tokens, NOISE_KEY,
        ref_config(config),
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(worst, grads, want_grads)
    )
    errors = {jax.tree_util.keystr(path): err for path, err in flat}
    return abs(float(loss) - float(want_loss)) / float(want_loss), errors


def test_logits_of_both_halves_match_the_reference():
    params, tokens = toy_params(), toy_tokens()
    xt, masked, _ = sdar.noise(tokens, NOISE_KEY, TOY)
    assert 0 < int(masked.sum()) < masked.size
    got = sdar.forward(params, tokens, xt, TOY)
    want = ref.forward(params, tokens, xt, ref_config(TOY))
    assert got.shape == (2, 2 * SEQ, TOY.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.std(want)) > 0.1  # the comparison is not of noise around 0
    assert worst(got[:, :SEQ], want[:, :SEQ]) < REASSOCIATION  # the clean half
    assert worst(got[:, SEQ:], want[:, SEQ:]) < REASSOCIATION  # the noised half
    # the clean half is the block-causal model of x0 alone: what the
    # noised half holds does not reach it
    other, _, _ = sdar.noise(tokens, jax.random.key(18), TOY)
    assert bool(jnp.any(other != xt))
    np.testing.assert_array_equal(
        np.asarray(sdar.forward(params, tokens, other, TOY)[:, :SEQ]),
        np.asarray(got[:, :SEQ]),
    )


def test_loss_and_every_gradient_leaf_match_the_reference():
    loss_error, errors = _grad_errors(TOY)
    assert loss_error < REASSOCIATION
    assert len(errors) == 3 * 11 + 3
    assert max(errors.values()) < REASSOCIATION, max(errors, key=errors.get)


def test_a_bfloat16_pass_fails_the_same_tolerances():
    """The tolerances would catch a program that computes in a lower
    precision than its configuration states."""
    loss_error, errors = _grad_errors(dataclasses.replace(SHORT, dtype=jnp.bfloat16))
    failing = [name for name, err in errors.items() if err > REASSOCIATION]
    assert loss_error > REASSOCIATION
    assert len(failing) > len(errors) // 2


def test_remat_changes_nothing():
    params, tokens = toy_params(SHORT), toy_tokens()
    plain = dataclasses.replace(SHORT, remat=False)
    a = jax.grad(sdar.loss_fn)(params, tokens, NOISE_KEY, SHORT)
    b = jax.grad(sdar.loss_fn)(params, tokens, NOISE_KEY, plain)
    assert max(jax.tree.leaves(jax.tree.map(worst, a, b))) < 1e-6


# ------------------------------------------------------------- objective


def test_the_noising_is_a_function_of_the_key_alone_and_a_time_a_block():
    tokens = toy_tokens(seq=32)
    xt, masked, t = sdar.noise(tokens, NOISE_KEY, TOY)
    again = sdar.noise(tokens, NOISE_KEY, TOY)
    for a, b in zip((xt, masked, t), again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    other = sdar.noise(tokens, jax.random.fold_in(NOISE_KEY, 1), TOY)
    assert bool(jnp.any(other[1] != masked)) and bool(jnp.any(other[2] != t))
    # one time a block of 4, within [1e-3, 1]
    blocks = np.asarray(t).reshape(2, 8, 4)
    assert (blocks == blocks[:, :, :1]).all() and len(np.unique(blocks)) == 16
    assert blocks.min() >= 1e-3 and blocks.max() <= 1.0
    # the mask id where masked, the token elsewhere; never drawn as a token
    assert TOY.mask_token_id == 63 and int(jnp.max(tokens)) < 63
    np.testing.assert_array_equal(
        np.asarray(xt), np.where(np.asarray(masked), 63, np.asarray(tokens))
    )
    # and the reference's own copy draws the same
    theirs = ref.noise(tokens, NOISE_KEY, ref_config(TOY))
    for a, b in zip((xt, masked, t), theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="no whole number of blocks"):
        sdar.noise(toy_tokens(seq=18), NOISE_KEY, TOY)


def test_the_loss_is_the_weighted_masked_cross_entropy_written_out():
    """(1 / L) sum over masked i of (1 / t_i) * CE(noised position i,
    x0[i]), unshifted, by hand in numpy from the program's logits."""
    params, tokens = toy_params(SHORT), toy_tokens()
    xt, masked, t = (np.asarray(a) for a in sdar.noise(tokens, NOISE_KEY, SHORT))
    logits = np.asarray(sdar.forward(params, tokens, jnp.asarray(xt), SHORT), np.float64)
    total = 0.0
    for b in range(tokens.shape[0]):
        for i in range(SEQ):
            if masked[b, i]:
                row = logits[b, SEQ + i]  # the noised copy of position i
                logp = row - (np.log(np.sum(np.exp(row - row.max()))) + row.max())
                total += -logp[int(tokens[b, i])] / t[b, i]
    want = total / SEQ / tokens.shape[0]
    got = float(sdar.loss_fn(params, tokens, NOISE_KEY, SHORT))
    assert got == pytest.approx(want, rel=1e-5) and want > 1.0


# ------------------------------------------------------------ attention


def token_by_token(half, block):
    """The mask from (half, block) of each of the 2 x half tokens, clean
    half first, written out pair by pair."""
    mask = np.zeros((2 * half, 2 * half), bool)
    for i in range(2 * half):
        for j in range(2 * half):
            i_noised, j_noised = i >= half, j >= half
            bi, bj = (i % half) // block, (j % half) // block
            if i_noised and j_noised:
                mask[i, j] = bi == bj
            elif i_noised:
                mask[i, j] = bj < bi
            elif not j_noised:
                mask[i, j] = bj <= bi
    return mask


@pytest.mark.parametrize("half", [8, 16, 24], ids=["below_a_tile", "a_tile", "above"])
@pytest.mark.parametrize("block", [1, 4, "L"])
def test_the_masks_against_one_built_token_by_token(half, block):
    block = half if block == "L" else block
    want = token_by_token(half, block)
    np.testing.assert_array_equal(block_diffusion_mask(half, block), want)
    _, noised, blk = ref.doubled(half, block)
    np.testing.assert_array_equal(
        np.asarray(ref.sees(noised[:, None], blk[:, None], noised[None, :], blk[None, :])),
        want,
    )
    assert want.any(axis=1).all() and not want[:half, half:].any()


@pytest.mark.parametrize("flash", [False, True], ids=["einsum", "flash"])
@pytest.mark.parametrize("half", [8, 16, 24], ids=["below_a_tile", "a_tile", "above"])
@pytest.mark.parametrize("block", [1, 4, "L"])
def test_attention_against_scores_masked_token_by_token(block, half, flash, monkeypatch):
    """One layer's attention (4 query heads over 2, query/key norms,
    rotary by position id) at a half below, at and above a 16-row tile
    (at 24 a tile holds clean and noised tokens), blocks of 1, 4 and the
    whole half, through the einsum form and through ``ops/attention.py``
    (interpreted), against numpy scores under the token-by-token mask."""
    monkeypatch.setenv("TPUSNAPSHOT_FLASH_BLOCK_CAP", "16")
    block = half if block == "L" else block
    config = dataclasses.replace(TOY, flash_attention=flash, block_length=block)
    blk = toy_params()["layers"][0]
    h = jax.random.normal(jax.random.key(9), (2, 2 * half, TOY.hidden_size))
    positions = jnp.tile(jnp.arange(half), 2)
    got = sdar.attention(h, blk, positions, config)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(h, blk, ref_config(config), query_block=8)
    assert worst(got, want) < REASSOCIATION
    # and the reference against numpy, the mask pair by pair
    heads, kv, hd = 4, 2, 16
    h64 = np.asarray(h, np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in blk.items()}

    def normed(x, weight):
        return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + 1e-6) * weight

    def rotated(x):
        out = np.empty_like(x)
        for p in range(2 * half):
            for i in range(hd // 2):
                angle = (p % half) * 100.0 ** (-2 * i / hd)
                a, b = x[:, p, :, i], x[:, p, :, i + hd // 2]
                out[:, p, :, i] = a * np.cos(angle) - b * np.sin(angle)
                out[:, p, :, i + hd // 2] = b * np.cos(angle) + a * np.sin(angle)
        return out

    q = rotated(normed((h64 @ w["wq"]).reshape(2, -1, heads, hd), w["q_norm"]))
    k = rotated(normed((h64 @ w["wk"]).reshape(2, -1, kv, hd), w["k_norm"]))
    v = (h64 @ w["wv"]).reshape(2, -1, kv, hd)
    k, v = np.repeat(k, heads // kv, axis=2), np.repeat(v, heads // kv, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    s = np.where(token_by_token(half, block), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    o = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)
    by_hand = o.reshape(2, 2 * half, heads * hd) @ w["wo"]
    assert worst(want, by_hand) < REASSOCIATION


def test_position_ids_and_not_indices_drive_the_rotary():
    """Token i of the noised half turns as token i of the clean half
    does; rotary by index (0 .. 2L-1) gives other logits."""
    x = jax.random.normal(jax.random.key(2), (1, 12, 3, 16))
    by_id = sdar.apply_rope(x, jnp.tile(jnp.arange(6), 2), 100.0)
    by_index = sdar.apply_rope(x, jnp.arange(12), 100.0)
    np.testing.assert_array_equal(np.asarray(by_id[:, :6]), np.asarray(by_index[:, :6]))
    assert worst(by_id[:, 6:], by_index[:, 6:]) > 0.1
    same = sdar.apply_rope(jnp.concatenate([x[:, :6], x[:, :6]], axis=1),
                           jnp.tile(jnp.arange(6), 2), 100.0)
    np.testing.assert_array_equal(np.asarray(same[:, :6]), np.asarray(same[:, 6:]))
    cos, sin = ref.rotary_tables(100.0, 16, np.tile(np.arange(6), 2))
    assert worst(by_id, ref.apply_rotary(x, cos, sin)) < 1e-6
    # in the model: the program under positions by index leaves the reference
    params, tokens = toy_params(SHORT), toy_tokens()
    xt, _, _ = sdar.noise(tokens, NOISE_KEY, SHORT)
    want = ref.forward(params, tokens, xt, ref_config(SHORT))
    h = params["embed"][jnp.concatenate([tokens, xt], axis=1)]
    by_index = sdar.layer(h, params["layers"][0], jnp.arange(2 * SEQ), SHORT)
    by_id = sdar.layer(h, params["layers"][0], jnp.tile(jnp.arange(SEQ), 2), SHORT)
    with jax.default_matmul_precision("highest"):
        ref_layer = ref.layer(h, params["layers"][0], ref_config(SHORT))
    assert worst(by_id, ref_layer) < REASSOCIATION < 1e-2 < worst(by_index, ref_layer)
    assert want.shape == (2, 2 * SEQ, 64)


# -------------------------------------------------------------- experts


def test_softmax_routing_against_a_hand_written_numpy_one():
    """softmax over all 32 experts in float32, the 3 largest, divided by
    their sum; no scaling factor, no bias."""
    blk = toy_params()["layers"][1]
    x = jax.random.normal(jax.random.key(5), (48, TOY.hidden_size))
    gates, routed = experts.held_gates(x, blk["router"], None, TOY.routing)
    logits = np.asarray(x, np.float64) @ np.asarray(blk["router"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.zeros((48, 4))
    for token in range(48):
        top = np.argsort(-p[token])[:3]
        for e in top:
            if e in TOY.expert_ids:
                want[token, TOY.expert_ids.index(e)] = p[token, e] / p[token, top].sum()
    np.testing.assert_allclose(np.asarray(gates), want, rtol=2e-5, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(routed), want > 0)
    assert 0 < (want > 0).sum() < 48 * 3
    # the reference's own gates agree, and a token's gates sum to 1
    theirs = ref.expert_gates(x, blk, ref_config(TOY))
    np.testing.assert_allclose(np.asarray(theirs)[:, 4:8], want, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(theirs).sum(-1), 1.0, rtol=1e-5)
    # sigmoid, the default, scores otherwise
    sigmoid = dataclasses.replace(TOY.routing, scoring="sigmoid")
    assert experts.Routing((0,), 1, True, 1.0).scoring == "sigmoid"
    other, _ = experts.held_gates(x, blk["router"], None, sigmoid)
    assert worst(other, gates) > 1e-2
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        dataclasses.replace(TOY.routing, scoring="tanh")


def test_expert_shares_add_up_to_the_uncut_layer():
    """All 8 shares of an expert layer (4 of 32 experts each; there is no
    shared expert to count once) give the reference's uncut layer: the
    share is a cut of the model, not another model."""
    blk = toy_params()["layers"][1]
    x = jax.random.normal(jax.random.key(5), (48, TOY.hidden_size))
    gate_up = 0.16 * jax.random.normal(
        jax.random.key(6), (32, TOY.hidden_size, 2 * TOY.moe_intermediate_size)
    )
    down = 0.16 * jax.random.normal(
        jax.random.key(7), (32, TOY.moe_intermediate_size, TOY.hidden_size)
    )
    whole = dict(blk, gate_up=gate_up, down=down)
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_experts(x, whole, ref_config(TOY), list(range(32)))
    total = jnp.zeros_like(x)
    for share in range(8):
        ids = tuple(range(4 * share, 4 * share + 4))
        held = dict(whole, gate_up=gate_up[jnp.array(ids)], down=down[jnp.array(ids)])
        part = sdar.routed_experts(x, held, dataclasses.replace(TOY, expert_ids=ids))
        with jax.default_matmul_precision("highest"):
            want = ref.routed_experts(x, held, ref_config(TOY), list(ids))
        assert worst(part, want) < REASSOCIATION or float(jnp.max(jnp.abs(want))) == 0
        total = total + part
    assert float(jnp.max(jnp.abs(uncut))) > 0.1
    assert worst(total, uncut) < REASSOCIATION
    gates = ref.expert_gates(x, whole, ref_config(TOY))
    assert int((gates > 0).sum()) == 48 * TOY.num_experts_per_tok


def test_gathered_experts_equal_dense_and_overflow_falls_back():
    """The capacity path under softmax gates: equal to every expert on
    every token when the slots suffice, and the dense computation when
    they do not (no token is dropped either way)."""
    blk = toy_params()["layers"][1]
    x = jax.random.normal(jax.random.key(8), (64, TOY.hidden_size))
    _, routed = experts.held_gates(x, blk["router"], None, TOY.routing)
    busiest = int(jnp.max(jnp.sum(routed, axis=0)))
    assert 2 <= busiest < 64
    dense = sdar.routed_experts(x, blk, TOY)
    roomy = sdar.routed_experts(x, blk, dataclasses.replace(TOY, expert_capacity=busiest))
    tight = sdar.routed_experts(
        x, blk, dataclasses.replace(TOY, expert_capacity=busiest - 1)
    )
    grouped = sdar.routed_experts(x, blk, dataclasses.replace(TOY, expert_dense_group=2))
    assert float(jnp.max(jnp.abs(dense))) > 0
    assert worst(roomy, dense) < REASSOCIATION and worst(grouped, dense) < REASSOCIATION
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(dense))
    grads = jax.grad(
        lambda b: jnp.sum(
            sdar.routed_experts(x, b, dataclasses.replace(TOY, expert_capacity=busiest))
        )
    )(blk)
    want = jax.grad(lambda b: jnp.sum(sdar.routed_experts(x, b, TOY)))(blk)
    for name in ("gate_up", "down", "router"):
        assert worst(grads[name], want[name]) < REASSOCIATION, name


# What ``experts.held_gates`` under sigmoid scoring (the default, what
# the Nemotron and Laguna cells run) lowered to at PR 35 (fe93898), the
# parent of the PR that taught ``Routing`` to score by softmax: sha256
# of ``jax.jit(...).lower(...).as_text()`` on the CPU backend, jax
# 0.9.0, computed on a ``git archive`` of that commit.
SIGMOID_GATES_AT_PR_35 = {
    "no_bias": (6169, "e7691d9d9ecc17f212124a0c518b4cd1f00f44390c2ea2206c1f69ceecd0d40a"),
    "bias": (6432, "12584eb7ed133b195a7c42ca10d238b59cb1de42dc54f7c52921af9a25f02b4a"),
}


@pytest.mark.parametrize("which", sorted(SIGMOID_GATES_AT_PR_35))
def test_sigmoid_routing_lowers_to_what_it_did_before_softmax(which):
    if jax.__version__ != "0.9.0":
        pytest.skip("the pinned text is jax 0.9.0's")
    x = jax.ShapeDtypeStruct((16, 32), jnp.bfloat16)
    router = jax.ShapeDtypeStruct((32, 8), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((8,), jnp.float32) if which == "bias" else None
    routing = experts.Routing((1, 2), top_k=2, normalise=True, scaling_factor=2.5)

    def held_gates(x, router, bias):
        return experts.held_gates(x, router, bias, routing)

    text = jax.jit(held_gates).lower(x, router, bias).as_text()
    length, digest = SIGMOID_GATES_AT_PR_35[which]
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == (length, digest)


# ---------------------------------------------------------------- state


def test_state_is_sixteen_bytes_a_parameter_with_gradients():
    config = dataclasses.replace(TOY, dtype=jnp.bfloat16)
    state = jax.eval_shape(lambda k: sdar.init_state(config, k), jax.random.key(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(state["master"]))
    saved = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(state)
    )
    assert saved == 14 * n_params + 4  # + the int32 count
    assert len(jax.tree.leaves(state)) == 4 * (3 * 11 + 3) + 1
    moments, count = state["opt"]
    assert type(moments).__name__ == "Moments" and count.dtype == jnp.int32
    assert {s.dtype for s in jax.tree.leaves(state["params"])} == {jnp.dtype(jnp.bfloat16)}
    blk = state["params"]["layers"][0]
    assert blk["wq"].shape == (32, 4 * 16) and blk["wk"].shape == (32, 2 * 16)
    assert blk["q_norm"].shape == blk["k_norm"].shape == (16,)
    assert blk["gate_up"].shape == (4, 32, 32) and blk["down"].shape == (4, 16, 32)
    assert blk["router"].shape == (32, 32)  # the router keeps its published width
    assert "shared_gate_up" not in blk
    grads = jax.eval_shape(
        lambda p, t: jax.grad(sdar.loss_fn)(p, t, NOISE_KEY, config),
        state["params"], toy_tokens(),
    )
    assert saved - 4 + sum(
        int(np.prod(g.shape)) * g.dtype.itemsize for g in jax.tree.leaves(grads)
    ) == 16 * n_params


def test_one_step_moves_every_part_of_the_state():
    config = dataclasses.replace(SHORT, dtype=jnp.bfloat16)
    hp = sdar.AdamW(lr=1e-2)
    state = sdar.init_state(config, jax.random.key(2))
    stepped, loss = jax.jit(
        lambda s, t, k: sdar.adamw_train_step(s, t, k, config, hp)
    )(state, toy_tokens(config), NOISE_KEY)
    assert np.isfinite(float(loss)) and int(stepped["opt"][1]) == 1
    assert jax.tree.structure(stepped) == jax.tree.structure(state)
    moved = jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)), state["master"], stepped["master"]
    )
    assert all(jax.tree.leaves(moved)), moved
    mu = stepped["opt"][0].mu
    assert all(bool(jnp.any(m != 0)) for m in jax.tree.leaves(mu))
