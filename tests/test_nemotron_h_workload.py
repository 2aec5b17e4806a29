"""``models/nemotron_h.py`` against the plain reference
(``perfbench/reference_nemotron_h.py``) at toy widths on the CPU, seeded
random weights.

The program is run in float32 here (``dtype=float32``), so what it is
compared with is the same arithmetic in another order: chunked scan
against the step-by-step recurrence, gathered or batched experts against
a loop, fused einsums against ``@``. Tolerances are therefore those of
float32 reassociation, and each is tight enough that the same program
computing in bfloat16 fails it (``test_a_bfloat16_pass_fails...``).
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import reference_nemotron_h as ref  # noqa: E402
from torchsnapshot_tpu.models import nemotron_h as nh  # noqa: E402

TOY = nh.NemotronHConfig(
    hidden_size=32,
    pattern="MEM*E",
    vocab_size=64,
    mamba_num_heads=4,
    mamba_head_dim=8,
    ssm_state_size=8,
    n_groups=2,
    chunk_size=8,
    n_routed_experts=32,
    expert_ids=(4, 5),
    num_experts_per_tok=3,
    moe_intermediate_size=16,
    moe_shared_expert_intermediate_size=24,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=8,
    flash_attention=False,
    dtype=jnp.float32,
)
SEQ = 20  # not a multiple of the chunk
SHORT = dataclasses.replace(TOY, pattern="ME*")  # one block a kind: compiles faster


def ref_config(config: nh.NemotronHConfig) -> dict:
    """The reference's plain dict, spelled with the published keys."""
    doc = dataclasses.asdict(config)
    doc["expert_ids"] = list(config.expert_ids)
    return doc


def toy_params(config=TOY, seed=0, scale=8.0):
    """Weights large enough (0.16 a matrix entry) that every mixer moves
    the residual stream: an error in one is not hidden by the others."""
    master = nh.init_master(config, jax.random.key(seed))
    master = jax.tree.map(lambda w: w * scale if w.ndim >= 2 else w, master)
    for blk in master["blocks"]:
        if "router_bias" in blk:
            blk["router_bias"] = 0.1 * jax.random.normal(
                jax.random.key(seed + 7), blk["router_bias"].shape
            )
    return jax.tree.map(lambda w: w.astype(config.dtype), master)


def toy_tokens(config=TOY, batch=2, seq=SEQ, seed=1):
    return jax.random.randint(jax.random.key(seed), (batch, seq), 0, config.vocab_size)


def worst(got, want):
    """Largest error of a leaf as a share of the leaf's largest entry."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


# Float32 reassociation: sums of up to a few hundred products in another
# order move a result by a few units of 2**-24 = 6e-8 relative to its
# largest term, and the errors add through five blocks and the backward
# pass. Measured here: 2e-6 at worst (an A_log gradient). A bfloat16 pass
# (8 bits of mantissa, 4e-3 a rounding) reads 1e-2 to 1e-1.
REASSOCIATION = 2e-5


def _grad_errors(config):
    params, tokens = toy_params(config), toy_tokens(config)
    loss, grads = jax.value_and_grad(nh.loss_fn)(params, tokens, config)
    want_loss, want_grads = jax.value_and_grad(ref.loss)(
        jax.tree.map(lambda p: p.astype(jnp.float32), params), tokens, ref_config(config)
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(worst, grads, want_grads)
    )
    errors = {jax.tree_util.keystr(path): err for path, err in flat}
    return abs(float(loss) - float(want_loss)) / float(want_loss), errors


def test_logits_match_the_reference():
    params, tokens = toy_params(), toy_tokens()
    got = nh.forward(params, tokens, TOY)
    want = ref.forward(params, tokens, ref_config(TOY))
    assert got.shape == (2, SEQ, TOY.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.std(want)) > 0.1  # the comparison is not of noise around 0
    assert worst(got, want) < REASSOCIATION


def test_loss_and_every_gradient_leaf_match_the_reference():
    loss_error, errors = _grad_errors(TOY)
    assert loss_error < REASSOCIATION
    assert len(errors) == 2 * 9 + 2 * 7 + 5 + 3
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
    a = jax.grad(nh.loss_fn)(params, tokens, SHORT)
    b = jax.grad(nh.loss_fn)(params, tokens, plain)
    assert max(jax.tree.leaves(jax.tree.map(worst, a, b))) < 1e-6


@pytest.mark.parametrize("seq", [8, 20, 37])
def test_chunked_scan_matches_the_recurrence(seq):
    """Chunks of 8 over lengths that are and are not multiples of 8,
    against the recurrence one step at a time. Decay over a chunk is as
    strong as exp(-8 * 0.7 * 4) here, so a wrong decay shows."""
    keys = jax.random.split(jax.random.key(3), 5)
    batch, heads, p, groups, n = 2, 4, 8, 2, 8
    x = jax.random.normal(keys[0], (batch, seq, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, heads)))
    a = -jnp.arange(1.0, heads + 1)
    b = jax.random.normal(keys[2], (batch, seq, groups, n))
    c = jax.random.normal(keys[3], (batch, seq, groups, n))
    got = nh.ssd_chunked(x, dt, a, b, c, chunk=8)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_recurrence(
            x, dt, a, jnp.repeat(b, heads // groups, 2), jnp.repeat(c, heads // groups, 2)
        )
    assert got.shape == want.shape == (batch, seq, heads, p)
    assert worst(got, want) < REASSOCIATION


def test_expert_shares_add_up_to_the_uncut_layer():
    """All 16 shares of an expert layer (2 of 32 experts each), with the
    shared expert counted once, give the reference's uncut layer: the
    share is a cut of the model, not another model."""
    params = toy_params()
    blk = params["blocks"][1]
    x = jax.random.normal(jax.random.key(5), (48, TOY.hidden_size))
    per_expert = 0.16 * jax.random.normal(
        jax.random.key(6), (32, TOY.hidden_size, TOY.moe_intermediate_size)
    )
    whole = dict(blk, up=per_expert, down=jnp.swapaxes(per_expert, 1, 2)[::-1])
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_experts(
            x, whole, ref_config(TOY), list(range(32))
        ) + ref.shared_expert(x, whole)
    total = nh.shared_expert(x, whole)
    for share in range(16):
        ids = (2 * share, 2 * share + 1)
        held = dict(whole, up=whole["up"][jnp.array(ids)], down=whole["down"][jnp.array(ids)])
        part = nh.routed_experts(x, held, dataclasses.replace(TOY, expert_ids=ids))
        with jax.default_matmul_precision("highest"):
            want = ref.routed_experts(x, held, ref_config(TOY), list(ids))
        assert worst(part, want) < REASSOCIATION or float(jnp.max(jnp.abs(want))) == 0
        total = total + part
    assert worst(total, uncut) < REASSOCIATION
    # Every token's three experts were somebody's: the gates of a token sum
    # to the routed scaling factor.
    gates = ref.expert_gates(x, whole, ref_config(TOY))
    np.testing.assert_allclose(gates.sum(-1), TOY.routed_scaling_factor, rtol=1e-5)


def test_gathered_experts_equal_dense_and_overflow_falls_back():
    """The capacity path is exact: equal to every expert on every token
    when the slots suffice, and the dense computation when they do not
    (no token is dropped either way)."""
    params = toy_params()
    blk = params["blocks"][1]
    x = jax.random.normal(jax.random.key(8), (64, TOY.hidden_size))
    gates, routed = nh.held_gates(x, blk, TOY)
    busiest = int(jnp.max(jnp.sum(routed, axis=0)))
    assert 2 <= busiest < 64
    dense = nh.routed_experts(x, blk, TOY)
    roomy = nh.routed_experts(x, blk, dataclasses.replace(TOY, expert_capacity=busiest))
    tight = nh.routed_experts(
        x, blk, dataclasses.replace(TOY, expert_capacity=busiest - 1)
    )
    gathered = nh._experts_gathered(x, gates, routed, blk["up"], blk["down"], busiest)
    assert float(jnp.max(jnp.abs(dense))) > 0
    assert worst(gathered, dense) < REASSOCIATION
    assert worst(roomy, dense) < REASSOCIATION
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(dense))
    # And its gradient flows to the stacked leaves.
    grads = jax.grad(
        lambda b: jnp.sum(
            nh.routed_experts(x, b, dataclasses.replace(TOY, expert_capacity=busiest))
        )
    )(blk)
    want = jax.grad(lambda b: jnp.sum(nh.routed_experts(x, b, TOY)))(blk)
    for name in ("up", "down", "router"):
        assert worst(grads[name], want[name]) < REASSOCIATION, name


def test_flash_attention_block_matches_the_einsum_block():
    """The ``*`` block through ``ops/attention.py`` (interpreted on the
    CPU) against its einsum form: grouped-query, causal. The kernel
    reassociates the softmax, float32 throughout."""
    config = dataclasses.replace(TOY, flash_attention=True)
    blk = toy_params()["blocks"][3]
    x = jax.random.normal(jax.random.key(9), (1, 16, TOY.hidden_size))
    got = nh.attention_mixer(x, blk, config)
    want = nh.attention_mixer(x, blk, TOY)
    assert worst(got, want) < REASSOCIATION


def test_state_is_sixteen_bytes_a_parameter_with_gradients():
    config = dataclasses.replace(TOY, dtype=jnp.bfloat16)
    state = jax.eval_shape(lambda k: nh.init_state(config, k), jax.random.key(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(state["master"]))
    saved = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(state)
    )
    assert saved == 14 * n_params + 4  # + the int32 count
    assert len(jax.tree.leaves(state)) == 4 * 40 + 1
    moments, count = state["opt"]
    assert isinstance(moments, nh.Moments) and count.dtype == jnp.int32
    assert {s.dtype for s in jax.tree.leaves(state["params"])} == {jnp.dtype(jnp.bfloat16)}
    held = state["params"]["blocks"][1]
    assert held["up"].shape == (2, 32, 16) and held["down"].shape == (2, 16, 32)
    assert held["router"].shape == (32, 32)  # the router keeps its published width


def test_one_adamw_step_against_a_hand_written_one():
    """Master, both moments, the count and the bfloat16 recast, leaf by
    leaf, against numpy float32, over two steps (the bias corrections
    differ). Only the order of a few float32 operations differs, and
    XLA may contract a multiply-add: 2e-6 of the leaf's scale."""
    config = dataclasses.replace(SHORT, dtype=jnp.bfloat16)
    hp = nh.AdamW(lr=1e-2, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    state = nh.init_state(config, jax.random.key(2))
    tokens = toy_tokens(config)
    update = jax.jit(lambda s, g: nh.adamw_update(s, g, hp))
    for expected_count in (1, 2):
        before = state
        loss, grads = jax.value_and_grad(nh.loss_fn)(before["params"], tokens, config)
        assert {g.dtype for g in jax.tree.leaves(grads)} == {jnp.dtype(jnp.bfloat16)}
        state = update(before, grads)
        (moments, count) = state["opt"]
        assert int(count) == expected_count and count.dtype == jnp.int32
        rows = zip(
            *(
                jax.tree.leaves(t)
                for t in (
                    before["master"], before["opt"][0].mu, before["opt"][0].nu, grads,
                    state["master"], moments.mu, moments.nu, state["params"],
                )
            )
        )
        for m0, mu0, nu0, g, m1, mu1, nu1, p1 in rows:
            want_m, want_mu, want_nu, _ = ref.adamw_step_numpy(
                np.asarray(m0), np.asarray(mu0), np.asarray(nu0), expected_count - 1,
                np.asarray(g.astype(jnp.float32)), dataclasses.asdict(hp),
            )
            assert worst(mu1, want_mu) < 2e-6
            assert worst(nu1, want_nu) < 2e-6
            assert worst(m1, want_m) < 2e-6
            if np.any(np.asarray(g.astype(jnp.float32))):  # the correction bias has none
                assert float(np.max(np.abs(np.asarray(m1) - np.asarray(m0)))) > 0
            # The compute copy is the master, rounded once.
            assert p1.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(p1.astype(jnp.float32)),
                np.asarray(m1.astype(jnp.bfloat16).astype(jnp.float32)),
            )
    # The whole step is that update on the step's own gradients.
    stepped, loss = jax.jit(lambda s, t: nh.adamw_train_step(s, t, config, hp))(
        before, tokens
    )
    assert np.isfinite(float(loss)) and int(stepped["opt"][1]) == 2
    assert jax.tree.structure(stepped) == jax.tree.structure(state)
