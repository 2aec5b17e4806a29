"""``models/laguna.py`` against the plain reference
(``perfbench/reference_laguna.py``) at toy widths on the CPU, seeded
random weights.

The program is run in float32 here (``dtype=float32``), so what it is
compared with is the same arithmetic in another order: fused gate and up
projections against two products, gathered or batched experts against a
loop, rotary on halves against ``rotate_half``, the kernel's tiled
softmax against materialised scores. Tolerances are therefore those of
float32 reassociation, and each is tight enough that the same program
computing in bfloat16 fails it (``test_a_bfloat16_pass_fails...``).
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import reference_laguna as ref  # noqa: E402
from torchsnapshot_tpu.models import laguna as lg  # noqa: E402
from torchsnapshot_tpu.models import nemotron_h as nh  # noqa: E402

WINDOW = 16
# YaRN at a toy size with a ramp that is not trivial: 4 frequencies, of
# which the first is extrapolated, the last interpolated, two blended.
TOY_YARN = lg.Rope(
    theta=100.0, partial_rotary_factor=0.5, factor=4.0,
    original_max_position_embeddings=16, beta_fast=2.0, beta_slow=0.25,
    attention_factor=None,
)
TOY = lg.LagunaConfig(
    hidden_size=32,
    layer_types=(lg.FULL, lg.SLIDING, lg.SLIDING, lg.FULL),
    mlp_layer_types=(lg.DENSE, lg.SPARSE, lg.SPARSE, lg.SPARSE),
    num_attention_heads_per_layer=(4, 6, 6, 4),
    vocab_size=64,
    num_key_value_heads=2,
    head_dim=16,
    sliding_window=WINDOW,
    max_position_embeddings=64,
    rope_full=TOY_YARN,
    rope_sliding=lg.Rope(theta=50.0),
    flash_attention=False,
    intermediate_size=48,
    num_experts=32,
    expert_ids=(4, 5, 6, 7),
    num_experts_per_tok=3,
    moe_intermediate_size=16,
    shared_expert_intermediate_size=24,
    dtype=jnp.float32,
)
SEQ = 20  # above the window, and no multiple of 8
SHORT = dataclasses.replace(
    TOY, layer_types=TOY.layer_types[:2], mlp_layer_types=TOY.mlp_layer_types[:2],
    num_attention_heads_per_layer=(4, 6),
)


def _rope_doc(rope: lg.Rope) -> dict:
    doc = {"rope_theta": rope.theta, "partial_rotary_factor": rope.partial_rotary_factor}
    if rope.factor is None:
        return dict(doc, rope_type="default")
    return dict(
        doc, rope_type="yarn", factor=rope.factor,
        original_max_position_embeddings=rope.original_max_position_embeddings,
        beta_fast=rope.beta_fast, beta_slow=rope.beta_slow,
        attention_factor=rope.attention_factor,
    )


def ref_config(config: lg.LagunaConfig) -> dict:
    """The reference's plain dict, spelled with the published keys."""
    doc = dataclasses.asdict(config)
    doc["rope_parameters"] = {
        lg.FULL: _rope_doc(config.rope_full),
        lg.SLIDING: _rope_doc(config.rope_sliding),
    }
    doc["expert_ids"] = list(config.expert_ids)
    return doc


def toy_params(config=TOY, seed=0, scale=8.0):
    """Weights large enough (0.16 a matrix entry) that every part of a
    layer moves the residual stream: an error in one is not hidden by
    the others."""
    master = lg.init_master(config, jax.random.key(seed))
    master = jax.tree.map(lambda w: w * scale if w.ndim >= 2 else w, master)
    return jax.tree.map(lambda w: w.astype(config.dtype), master)


def toy_tokens(config=TOY, batch=2, seq=SEQ, seed=1):
    return jax.random.randint(jax.random.key(seed), (batch, seq), 0, config.vocab_size)


def worst(got, want):
    """Largest error of a leaf as a share of the leaf's largest entry."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


# Float32 reassociation: sums of up to a few hundred products in another
# order move a result by a few units of 2**-24 = 6e-8 relative to its
# largest term, and the errors add through four layers and the backward
# pass. Measured here: 3e-6 at worst. A bfloat16 pass (8 bits of
# mantissa, 4e-3 a rounding) reads 1e-2 to 1e-1.
REASSOCIATION = 2e-5


def _grad_errors(config):
    params, tokens = toy_params(config), toy_tokens(config)
    loss, grads = jax.value_and_grad(lg.loss_fn)(params, tokens, config)
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
    got = lg.forward(params, tokens, TOY)
    want = ref.forward(params, tokens, ref_config(TOY))
    assert got.shape == (2, SEQ, TOY.vocab_size) and got.dtype == jnp.float32
    assert float(jnp.std(want)) > 0.1  # the comparison is not of noise around 0
    assert worst(got, want) < REASSOCIATION


def test_loss_and_every_gradient_leaf_match_the_reference():
    loss_error, errors = _grad_errors(TOY)
    assert loss_error < REASSOCIATION
    assert len(errors) == 9 + 3 * 12 + 3
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
    a = jax.grad(lg.loss_fn)(params, tokens, SHORT)
    b = jax.grad(lg.loss_fn)(params, tokens, plain)
    assert max(jax.tree.leaves(jax.tree.map(worst, a, b))) < 1e-6


# ------------------------------------------------------------ attention


@pytest.mark.parametrize("flash", [False, True], ids=["einsum", "flash"])
@pytest.mark.parametrize("seq", [8, WINDOW, 24], ids=["below", "at", "above"])
@pytest.mark.parametrize("layer", [0, 1], ids=[lg.FULL, lg.SLIDING])
def test_attention_of_each_kind_against_a_mask_built_from_positions(layer, seq, flash):
    """One layer's attention, both kinds (4 and 6 query heads over 2),
    at lengths below, at and above the window, through the einsum form
    and through ``ops/attention.py`` (interpreted), against the
    reference's scores masked from positions."""
    config = dataclasses.replace(TOY, flash_attention=flash)
    blk = toy_params()["layers"][layer]
    kind = TOY.layer_types[layer]
    h = jax.random.normal(jax.random.key(9), (2, seq, TOY.hidden_size))
    got = lg.attention(h, blk, kind, config)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(
            h, blk, kind, TOY.num_attention_heads_per_layer[layer], ref_config(TOY),
            query_block=8,
        )
    assert worst(got, want) < REASSOCIATION
    if kind == lg.SLIDING and seq > WINDOW:
        # and the window is felt: full attention over the same leaves differs
        unwindowed = lg.attention(h, blk, lg.FULL, dataclasses.replace(
            config, rope_full=config.rope_sliding))
        assert worst(unwindowed, want) > 1e-2


# --------------------------------------------------------------- rotary


def _numpy_yarn(theta, dim, factor, original, beta_fast, beta_slow):
    """YaRN's inverse frequencies, hand-written: dimension i turns
    ``original / (2 pi theta^(2i/dim))`` times over the original context;
    those that turn at least ``beta_fast`` times keep their frequency,
    those that turn at most ``beta_slow`` times have it divided by
    ``factor``, with a linear ramp over the (rounded outward) indices
    between."""
    def index_that_turns(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(index_that_turns(beta_fast)), 0)
    high = min(math.ceil(index_that_turns(beta_slow)), dim - 1)
    out = np.empty(dim // 2)
    for i in range(dim // 2):
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        plain = theta ** (-2.0 * i / dim)
        out[i] = keep * plain + (1.0 - keep) * plain / factor
    return out


def test_yarn_frequencies_at_the_published_parameters_against_numpy():
    rope = lg.LagunaConfig().rope_full
    inv_freq, attention_factor = lg.rope_inv_freq(rope, 128, 262144)
    want = _numpy_yarn(500000.0, 64, 64.0, 4096, 64.0, 1.0)
    assert inv_freq.shape == (32,) and inv_freq.dtype == np.float32
    np.testing.assert_allclose(inv_freq, want, rtol=2e-6)
    # the fast dimensions are untouched, the slow ones divided by 64
    np.testing.assert_allclose(inv_freq[0], 1.0)
    np.testing.assert_allclose(inv_freq[-1], 500000.0 ** (-62 / 64) / 64, rtol=2e-6)
    assert attention_factor == 1.4158883083359672
    # which is what the family's formula gives where the file gave none
    derived = lg.rope_inv_freq(dataclasses.replace(rope, attention_factor=None), 128, 262144)[1]
    assert derived == pytest.approx(0.1 * math.log(64.0) + 1.0, rel=1e-12)
    assert derived == pytest.approx(1.4158883083359672, rel=1e-12)
    # the reference's own copy agrees
    theirs, factor = ref.yarn_inv_freq(
        ref_config(lg.LagunaConfig())["rope_parameters"][lg.FULL], 128, 262144
    )
    np.testing.assert_allclose(theirs, want, rtol=2e-6)
    assert factor == attention_factor
    # and the toy's ramp blends: neither end on every frequency
    toy, _ = lg.rope_inv_freq(TOY_YARN, 16, 64)
    plain = 100.0 ** (-np.arange(0, 8, 2) / 8)
    ratio = toy / plain
    assert ratio[0] == pytest.approx(1.0) and ratio[-1] == pytest.approx(0.25)
    assert 0.25 < ratio[1] < 1.0 and 0.25 < ratio[2] < 1.0


@pytest.mark.parametrize("kind", [lg.FULL, lg.SLIDING])
def test_rotary_against_a_hand_written_numpy_one(kind):
    """Position p turns the pair (x[i], x[i + rot/2]) by ``p * inv_freq[i]``
    and scales it by the attention factor; dimensions past ``rot`` pass."""
    rope = TOY.rope_full if kind == lg.FULL else TOY.rope_sliding
    inv_freq, factor = lg.rope_inv_freq(rope, TOY.head_dim, TOY.max_position_embeddings)
    rot = 2 * len(inv_freq)
    assert rot == (8 if kind == lg.FULL else 16)
    x = np.asarray(jax.random.normal(jax.random.key(2), (2, 11, 3, TOY.head_dim)))
    want = x.copy()
    for p in range(x.shape[1]):
        for i in range(rot // 2):
            angle = p * float(inv_freq[i])
            a, b = x[:, p, :, i], x[:, p, :, i + rot // 2]
            want[:, p, :, i] = factor * (a * math.cos(angle) - b * math.sin(angle))
            want[:, p, :, i + rot // 2] = factor * (b * math.cos(angle) + a * math.sin(angle))
    got = lg.apply_rope(jnp.asarray(x), inv_freq, factor)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    cos, sin = ref.rotary_tables(
        ref_config(TOY)["rope_parameters"][kind], TOY.head_dim,
        TOY.max_position_embeddings, x.shape[1],
    )
    np.testing.assert_allclose(
        np.asarray(ref.apply_rotary(jnp.asarray(x), cos, sin)), want, atol=2e-6
    )
    if kind == lg.FULL:
        assert factor == pytest.approx(0.1 * math.log(4.0) + 1.0)
        np.testing.assert_array_equal(np.asarray(got)[..., rot:], x[..., rot:])


# -------------------------------------------------------------- experts


def test_expert_shares_add_up_to_the_uncut_layer():
    """All 8 shares of an expert layer (4 of 32 experts each), with the
    shared expert counted once, give the reference's uncut layer: the
    share is a cut of the model, not another model."""
    params = toy_params()
    blk = params["layers"][1]
    x = jax.random.normal(jax.random.key(5), (48, TOY.hidden_size))
    gate_up = 0.16 * jax.random.normal(
        jax.random.key(6), (32, TOY.hidden_size, 2 * TOY.moe_intermediate_size)
    )
    down = 0.16 * jax.random.normal(
        jax.random.key(7), (32, TOY.moe_intermediate_size, TOY.hidden_size)
    )
    whole = dict(blk, gate_up=gate_up, down=down)
    with jax.default_matmul_precision("highest"):
        uncut = ref.routed_experts(
            x, whole, ref_config(TOY), list(range(32))
        ) + ref.shared_expert(x, whole)
    total = lg.shared_expert(x, whole)
    for share in range(8):
        ids = tuple(range(4 * share, 4 * share + 4))
        held = dict(whole, gate_up=gate_up[jnp.array(ids)], down=down[jnp.array(ids)])
        part = lg.routed_experts(x, held, dataclasses.replace(TOY, expert_ids=ids))
        with jax.default_matmul_precision("highest"):
            want = ref.routed_experts(x, held, ref_config(TOY), list(ids))
        assert worst(part, want) < REASSOCIATION or float(jnp.max(jnp.abs(want))) == 0
        total = total + part
    assert worst(total, uncut) < REASSOCIATION
    # Every token's three experts were somebody's: the gates of a token sum
    # to the routed scaling factor.
    gates = ref.expert_gates(x, whole, ref_config(TOY))
    np.testing.assert_allclose(gates.sum(-1), TOY.moe_routed_scaling_factor, rtol=1e-5)
    assert int((gates > 0).sum()) == 48 * TOY.num_experts_per_tok


def test_gathered_experts_equal_dense_and_overflow_falls_back():
    """The capacity path is exact under a SwiGLU body too: equal to every
    expert on every token when the slots suffice, and the dense
    computation when they do not (no token is dropped either way)."""
    from torchsnapshot_tpu.models import experts

    blk = toy_params()["layers"][1]
    x = jax.random.normal(jax.random.key(8), (64, TOY.hidden_size))
    gates, routed = experts.held_gates(x, blk["router"], None, TOY.routing)
    busiest = int(jnp.max(jnp.sum(routed, axis=0)))
    assert 2 <= busiest < 64
    dense = lg.routed_experts(x, blk, TOY)
    roomy = lg.routed_experts(x, blk, dataclasses.replace(TOY, expert_capacity=busiest))
    tight = lg.routed_experts(
        x, blk, dataclasses.replace(TOY, expert_capacity=busiest - 1)
    )
    assert float(jnp.max(jnp.abs(dense))) > 0
    assert worst(roomy, dense) < REASSOCIATION
    np.testing.assert_array_equal(np.asarray(tight), np.asarray(dense))
    grads = jax.grad(
        lambda b: jnp.sum(
            lg.routed_experts(x, b, dataclasses.replace(TOY, expert_capacity=busiest))
        )
    )(blk)
    want = jax.grad(lambda b: jnp.sum(lg.routed_experts(x, b, TOY)))(blk)
    for name in ("gate_up", "down", "router"):
        assert worst(grads[name], want[name]) < REASSOCIATION, name


@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_dense_computation_in_groups_equals_all_at_once(group):
    """``Routing.dense_group``: the held experts a few at a time, each
    group recomputed in the backward pass, give what all at once give,
    in the result and in every gradient; a group that does not divide
    the experts held is refused."""
    from torchsnapshot_tpu.models import experts

    blk = toy_params()["layers"][1]
    x = jax.random.normal(jax.random.key(8), (24, TOY.hidden_size))

    def run(b, x, g):
        return lg.routed_experts(x, b, dataclasses.replace(TOY, expert_dense_group=g))

    assert worst(run(blk, x, group), run(blk, x, 0)) < REASSOCIATION
    got = jax.grad(lambda b, x: jnp.sum(run(b, x, group) ** 2), argnums=(0, 1))(blk, x)
    want = jax.grad(lambda b, x: jnp.sum(run(b, x, 0) ** 2), argnums=(0, 1))(blk, x)
    for name in ("gate_up", "down", "router"):
        assert worst(got[0][name], want[0][name]) < REASSOCIATION, name
    assert worst(got[1], want[1]) < REASSOCIATION
    if group == 1:
        gates, _ = experts.held_gates(x, blk["router"], None, TOY.routing)
        with pytest.raises(ValueError, match="does not divide"):
            experts.experts_dense(x, gates, lambda project: project(blk["gate_up"]), blk["down"], 3)


def test_both_models_route_through_the_same_layer():
    """What the sharing is for: ``nemotron_h`` and ``laguna`` call one
    ``experts.routed_experts``, and step with one ``adamw_update``."""
    from torchsnapshot_tpu.models import experts, mixed_adamw

    assert nh.adamw_update is mixed_adamw.adamw_update is lg.adamw_update
    assert nh.Moments is mixed_adamw.Moments and nh.AdamW is mixed_adamw.AdamW
    x = jax.random.normal(jax.random.key(3), (16, 32))
    routing = experts.Routing((1, 2), top_k=2, normalise=True, scaling_factor=2.5)
    router = jax.random.normal(jax.random.key(4), (32, 8))
    up = 0.2 * jax.random.normal(jax.random.key(5), (2, 32, 16))
    down = 0.2 * jax.random.normal(jax.random.key(6), (2, 16, 32))
    config = nh.NemotronHConfig(
        hidden_size=32, n_routed_experts=8, expert_ids=(1, 2), num_experts_per_tok=2
    )
    blk = {"router": router, "router_bias": jnp.zeros((8,)), "up": up, "down": down}
    np.testing.assert_array_equal(
        np.asarray(nh.routed_experts(x, blk, config)),
        np.asarray(
            experts.routed_experts(
                x, router, jnp.zeros((8,)), lambda project: nh._relu2(project(up)),
                down, routing,
            )
        ),
    )


# ---------------------------------------------------------------- state


def test_state_is_sixteen_bytes_a_parameter_with_gradients():
    config = dataclasses.replace(TOY, dtype=jnp.bfloat16)
    state = jax.eval_shape(lambda k: lg.init_state(config, k), jax.random.key(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(state["master"]))
    saved = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(state)
    )
    assert saved == 14 * n_params + 4  # + the int32 count
    assert len(jax.tree.leaves(state)) == 4 * (9 + 3 * 12 + 3) + 1
    moments, count = state["opt"]
    assert isinstance(moments, nh.Moments) and count.dtype == jnp.int32
    assert {s.dtype for s in jax.tree.leaves(state["params"])} == {jnp.dtype(jnp.bfloat16)}
    full, sliding = state["params"]["layers"][0], state["params"]["layers"][1]
    # a layer's leaves differ in shape by kind
    assert full["wq"].shape == (32, 4 * 16) and sliding["wq"].shape == (32, 6 * 16)
    assert full["wg"].shape == (32, 4) and sliding["wg"].shape == (32, 6)
    assert full["wk"].shape == sliding["wk"].shape == (32, 2 * 16)
    assert full["gate_up"].shape == (32, 2 * 48) and "router" not in full
    assert sliding["gate_up"].shape == (4, 32, 32) and sliding["down"].shape == (4, 16, 32)
    assert sliding["router"].shape == (32, 32)  # the router keeps its published width
    # and with a step's gradients in the compute dtype: 16 B a parameter
    grads = jax.eval_shape(
        lambda p, t: jax.grad(lg.loss_fn)(p, t, config), state["params"], toy_tokens()
    )
    assert saved - 4 + sum(
        int(np.prod(g.shape)) * g.dtype.itemsize for g in jax.tree.leaves(grads)
    ) == 16 * n_params


def test_one_step_moves_every_part_of_the_state():
    config = dataclasses.replace(SHORT, dtype=jnp.bfloat16)
    hp = lg.AdamW(lr=1e-2)
    state = lg.init_state(config, jax.random.key(2))
    stepped, loss = jax.jit(lambda s, t: lg.adamw_train_step(s, t, config, hp))(
        state, toy_tokens(config)
    )
    assert np.isfinite(float(loss)) and int(stepped["opt"][1]) == 1
    assert jax.tree.structure(stepped) == jax.tree.structure(state)
    moved = jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)), state["master"], stepped["master"]
    )
    assert all(jax.tree.leaves(moved)), moved
    mu = stepped["opt"][0].mu
    assert all(bool(jnp.any(m != 0)) for m in jax.tree.leaves(mu))
