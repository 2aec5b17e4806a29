"""The plain reference of the ``laguna`` stack: forward, loss and
gradients in straightforward float32 ``jax.numpy``, the benchmark's own
copy, importing nothing of the program.

It follows the published description of
https://huggingface.co/poolside/Laguna-XS.2 (``config.json``,
``model_type`` ``laguna``) layer by layer; one layer, on ``x`` [B, T, d],
``H = num_attention_heads_per_layer[l]``:

- ``h = rmsnorm(x)``; ``q = h Wq`` (H heads), ``k = h Wk``, ``v = h Wv``
  (``num_key_value_heads`` heads), no bias.
- Rotary on q and k, positions 0..T-1, in the ``rotate_half`` form
  (``x cos + rotate_half(x) sin`` on the first ``partial_rotary_factor``
  of a head's dimensions, halves not pairs). ``full_attention``: YaRN
  frequencies as ``transformers`` computes them, written out in
  :func:`yarn_inv_freq`, cos and sin times ``attention_factor``.
  ``sliding_attention``: ``theta ** (-2 i / dim)``, unscaled.
- ``s = q k^T / sqrt(head_dim)``; key j visible to query i iff ``j <= i``
  and, on sliding layers, ``i - j < sliding_window``: the mask is built
  from positions; the scores are materialised, the queries a block at a
  time so that a long sequence fits. ``o = softmax(s) v``.
- ``g = sigmoid(h Wg)`` [B, T, H]; head a of ``o`` times ``g[..., a]``;
  ``x <- x + concat(o) Wo``.
- ``h2 = rmsnorm(x)``. ``dense``: ``x <- x + (silu(h2 W_gate) * h2
  W_up) W_down``. ``sparse``: ``r = sigmoid(h2 Wr)`` over all
  ``num_experts``; the ``num_experts_per_tok`` largest; their scores
  divided by their sum and times ``moe_routed_scaling_factor``; the
  experts as a Python loop over the ids given, each applied to every
  token and weighted by its gate, plus the shared expert. Given all ids
  it is the uncut layer; given a chip's share, that share's part.

Embedding and untied head over the rows held, a final rmsnorm, mean
next-token cross entropy. Everything runs under
``jax.default_matmul_precision("highest")``. ``config`` is a plain dict
with the published keys of ``config.json``, of which ``layer_types``,
``mlp_layer_types`` and ``num_attention_heads_per_layer`` list the layers
held, ``num_experts`` is the router's width, and ``expert_ids`` and
``vocab_size`` are what is held. ``params`` is the program's parameter
tree (gate and up projections fused in one leaf, gate first); every leaf
is cast to float32 first, so the reference sees the values the program
computes with.

Departures from the source: none that the published config decides
(each expert of the loop and each block of queries is recomputed in the
backward pass: memory, not arithmetic).
What it leaves open is listed under ``assumed`` in the configuration's
file (the per-head gate read from ``gating: true``, ``silu``, the
router's sigmoid and normalisation, no correction bias, no query/key
norm).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _swiglu(x, gate_up, down):
    f = gate_up.shape[-1] // 2
    return (_silu(x @ gate_up[:, :f]) * (x @ gate_up[:, f:])) @ down


def yarn_inv_freq(rope, head_dim, max_position_embeddings):
    """``(inv_freq, attention_factor)`` of a ``rope_type`` ``yarn`` entry
    of ``rope_parameters``, step by step as
    ``transformers.modeling_rope_utils._compute_yarn_parameters``."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = rope["rope_theta"]
    factor = rope["factor"]
    original = rope.get("original_max_position_embeddings")
    if original:
        factor = max_position_embeddings / original
    else:
        original = max_position_embeddings
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 1.0 if factor <= 1 else 0.1 * math.log(factor) + 1.0
    beta_fast = rope.get("beta_fast") or 32
    beta_slow = rope.get("beta_slow") or 1

    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base)
        )

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0) for i in range(dim // 2)]
    inv_freq = []
    for i, r in enumerate(ramp):
        pos_freq = base ** (2 * i / dim)
        extrapolation_factor = 1.0 - r
        inv_freq.append(
            (1.0 / (factor * pos_freq)) * (1.0 - extrapolation_factor)
            + (1.0 / pos_freq) * extrapolation_factor
        )
    return np.asarray(inv_freq, np.float32), float(attention_factor)


def rotary_tables(rope, head_dim, max_position_embeddings, t):
    """``cos``, ``sin`` [t, rotary_dim], each the half-table twice."""
    if rope.get("rope_type", "default") == "yarn":
        inv_freq, scale = yarn_inv_freq(rope, head_dim, max_position_embeddings)
    else:
        dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
        inv_freq = np.asarray(
            [rope["rope_theta"] ** (-2 * i / dim) for i in range(dim // 2)], np.float32
        )
        scale = 1.0
    freqs = jnp.arange(t, dtype=_F32)[:, None] * jnp.asarray(inv_freq)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def apply_rotary(x, cos, sin):
    """``x`` [B, T, H, head_dim]; the first ``cos.shape[-1]`` dimensions
    of each head turn, the rest pass."""
    rot = cos.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    rotate_half = jnp.concatenate([-x_rot[..., half:], x_rot[..., :half]], axis=-1)
    x_rot = x_rot * cos[None, :, None, :] + rotate_half * sin[None, :, None, :]
    return jnp.concatenate([x_rot, x_pass], axis=-1)


def attention(h, blk, kind, heads, config, query_block=None):
    """``query_block`` (or the config's ``query_block``, or 1024): the
    queries whose scores exist at once (memory, not arithmetic)."""
    query_block = query_block or config.get("query_block", 1024)
    kv, hd = config["num_key_value_heads"], config["head_dim"]
    batch, t, _ = h.shape
    rope = config["rope_parameters"][kind]
    cos, sin = rotary_tables(rope, hd, config["max_position_embeddings"], t)
    q = apply_rotary((h @ blk["wq"]).reshape(batch, t, heads, hd), cos, sin)
    k = apply_rotary((h @ blk["wk"]).reshape(batch, t, kv, hd), cos, sin)
    v = (h @ blk["wv"]).reshape(batch, t, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=2)  # query head a reads kv head a // (H / kv)
    v = jnp.repeat(v, heads // kv, axis=2)
    window = config["sliding_window"] if kind == "sliding_attention" else None

    query_block = math.gcd(t, query_block)

    @jax.checkpoint
    def rows(block):
        q_rows, first = block  # [B, query_block, H, hd], the first row's position
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / np.sqrt(hd)
        i = (first + jnp.arange(query_block))[:, None]
        j = jnp.arange(t)[None, :]
        visible = j <= i
        if window is not None:
            visible = visible & (i - j < window)
        scores = jnp.where(visible, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    # One block after another (a scan, not a list: the compiler would
    # else keep many blocks' scores alive at once).
    blocks = q.reshape(batch, t // query_block, query_block, heads, hd)
    out = jax.lax.map(
        rows, (jnp.moveaxis(blocks, 1, 0), jnp.arange(0, t, query_block))
    )
    out = jnp.moveaxis(out, 0, 1).reshape(batch, t, heads, hd)
    gate = jax.nn.sigmoid(h @ blk["wg"])  # [B, T, H]
    out = out * gate[..., None]
    return out.reshape(batch, t, heads * hd) @ blk["wo"]


def expert_gates(x, blk, config):
    """[tokens, num_experts]: the weight of every expert in every token's
    result, 0 outside the token's top k."""
    scores = jax.nn.sigmoid(x @ blk["router"])
    order = jnp.argsort(-scores, axis=-1)
    chosen = order[:, : config["num_experts_per_tok"]]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * config["moe_routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weights)


def routed_experts(x, blk, config, expert_ids):
    """The part of the layer's result that the experts ``expert_ids``
    give; ``blk["gate_up"]``, ``blk["down"]`` are stacked in that order."""
    gates = expert_gates(x, blk, config)
    out = jnp.zeros_like(x)
    one_expert = jax.checkpoint(_swiglu)  # recomputed in the backward pass: memory only
    for held, expert in enumerate(expert_ids):
        y = one_expert(x, blk["gate_up"][held], blk["down"][held])
        out = out + gates[:, expert, None] * y
    return out


def shared_expert(x, blk):
    return _swiglu(x, blk["shared_gate_up"], blk["shared_down"])


def sparse_mlp(h, blk, config):
    flat = h.reshape(-1, h.shape[-1])
    out = routed_experts(flat, blk, config, config["expert_ids"]) + shared_expert(
        flat, blk
    )
    return out.reshape(h.shape)


def layer(x, blk, index, config):
    eps = config["rms_norm_eps"]
    kind = config["layer_types"][index]
    heads = config["num_attention_heads_per_layer"][index]
    x = x + attention(_rms_norm(x, blk["attn_norm"], eps), blk, kind, heads, config)
    h = _rms_norm(x, blk["mlp_norm"], eps)
    if config["mlp_layer_types"][index] == "dense":
        return x + _swiglu(h, blk["gate_up"], blk["down"])
    return x + sparse_mlp(h, blk, config)


def forward(params, tokens, config, remat=False):
    """Logits [B, T, V] over the vocabulary rows held. ``remat``
    recomputes each layer in the backward pass (memory only)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(_F32), params)
        x = params["embed"][tokens]
        for index, blk in enumerate(params["layers"]):
            run = lambda x, blk, index=index: layer(x, blk, index, config)
            x = (jax.checkpoint(run) if remat else run)(x, blk)
        x = _rms_norm(x, params["final_norm"], config["rms_norm_eps"])
        return x @ params["head"]


def loss(params, tokens, config, remat=False):
    """Next-token cross entropy over the held rows."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, tokens, config, remat)[:, :-1]
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(picked)
