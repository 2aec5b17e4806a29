"""The plain reference of the ``lfm2_moe`` stack, its next-token loss and
the update of its expert biases, in straightforward float32
``jax.numpy``: the benchmark's own copy, importing nothing of the
program.

It follows https://huggingface.co/LiquidAI/LFM2-8B-A1B (``config.json``,
``model_type`` ``lfm2_moe``) layer by layer; one layer, on ``x`` [B, T,
d], eps ``norm_eps``, no bias anywhere:

- ``h = rmsnorm(x)``, then the layer's operator, ``layer_types[l]``:
  ``conv``: ``[B, C, X]`` the three thirds of ``h W_in`` in that order,
  ``u = B * X``, ``v_t = sum over lags l < conv_L_cache of k[L-1-l] *
  u_{t-l}`` (``u`` zero before the first token), ``(C * v) W_out``;
  ``full_attention``: ``q = h Wq`` (``num_attention_heads`` heads of
  ``hidden_size / num_attention_heads``), ``k = h Wk``, ``v = h Wv``
  (``num_key_value_heads`` heads), ``q`` and ``k`` each normed per head
  by an RMS norm with a learned weight, rotary on every dimension,
  ``x cos + rotate_half(x) sin``, ``theta ** (-2 i / head_dim)``;
  ``s = q k^T / sqrt(head_dim)``, causal, the queries a block at a time
  so that a long sequence fits; ``o = softmax(s) v``, query head a
  reading key/value head ``a // (heads / kv heads)``; ``concat(o) Wo``.
  ``x <- x + that``.
- ``h2 = rmsnorm(x)``; the first ``num_dense_layers`` layers: ``(silu(h2
  Wg) * h2 Wu) Wd`` of ``intermediate_size``. Every later one: ``s =
  sigmoid(h2 Wr)`` over all the router's experts; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b`` the layer's expert
  bias) chosen; their ``s`` divided by their sum (``norm_topk_prob``)
  and times ``routed_scaling_factor``; the experts as a Python loop
  over the ids given, each ``(silu(h2 Wg) * h2 Wu) Wd`` on every token
  and weighted by its gate. Given all ids it is the uncut layer; given
  a chip's share, that share's part. No shared expert.

Embedding over the rows held, a final rmsnorm, the head the embedding
transposed (tied); ``loss`` the mean next-token cross entropy over the
held rows. A sparse layer's load is how many tokens chose each of the
router's experts; after a step ``b_i <- b_i + rate * sign(mean load -
load_i)`` (:func:`update_bias`).

Everything runs under ``jax.default_matmul_precision("highest")``.
``config`` is a plain dict with the published keys of ``config.json``,
of which ``expert_ids`` and ``vocab_size`` are what is held and
``layers_held`` how many of ``layer_types`` are; the router's width is
its leaf's. ``params`` is the program's parameter tree (gate and up
projections fused in one leaf, gate first); every leaf is cast to
float32 first, so the reference sees the values the program computes
with. ``expert_bias`` is one ``[router width]`` a sparse layer, or one
``[tokens, router width]`` to choose per token.

Departures from the source: none that the published config decides
(each expert of the loop and each block of queries is recomputed in the
backward pass: memory, not arithmetic). What it leaves open is listed
under ``assumed`` in the configuration's file (the tied head, the bias
update and its rate, the head width, the initial values).
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
    return (_silu(x @ gate_up[..., :f]) * (x @ gate_up[..., f:])) @ down


def layer_kinds(config):
    """``(operator, sparse?)`` of each layer held."""
    kinds = config["layer_types"][: config["layers_held"]]
    return [(kind, i >= config["num_dense_layers"]) for i, kind in enumerate(kinds)]


# ------------------------------------------------------------- operators


def short_conv(h, blk, config):
    """``h`` [B, T, d]: the gated short convolution."""
    d = h.shape[-1]
    proj = h @ blk["in_proj"]
    b, c, x = proj[..., :d], proj[..., d : 2 * d], proj[..., 2 * d :]
    u = b * x
    taps = config["conv_L_cache"]
    v = jnp.zeros_like(u)
    for lag in range(taps):
        earlier = jnp.pad(u, [(0, 0), (lag, 0), (0, 0)])[:, : u.shape[1]]
        v = v + blk["conv_w"][taps - 1 - lag] * earlier
    return (c * v) @ blk["out_proj"]


def rotary_tables(theta, head_dim, length):
    """``cos``, ``sin`` [T, head_dim], each the half-table twice."""
    inv_freq = np.asarray(
        [theta ** (-2 * i / head_dim) for i in range(head_dim // 2)], np.float32
    )
    freqs = jnp.arange(length, dtype=_F32)[:, None] * jnp.asarray(inv_freq)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rotary(x, cos, sin):
    """``x`` [B, T, H, head_dim]: ``x cos + rotate_half(x) sin``."""
    half = x.shape[-1] // 2
    rotate_half = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotate_half * sin[None, :, None, :]


def attention(h, blk, config, query_block=None):
    """``h`` [B, T, d]. ``query_block`` (or the config's, or 1024): the
    queries whose scores exist at once (memory, not arithmetic)."""
    query_block = query_block or config.get("query_block", 1024)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["hidden_size"] // heads
    eps = config["norm_eps"]
    batch, t, _ = h.shape
    cos, sin = rotary_tables(config["rope_theta"], hd, t)
    q = _rms_norm((h @ blk["wq"]).reshape(batch, t, heads, hd), blk["q_norm"], eps)
    k = _rms_norm((h @ blk["wk"]).reshape(batch, t, kv, hd), blk["k_norm"], eps)
    q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
    v = (h @ blk["wv"]).reshape(batch, t, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    query_block = math.gcd(t, query_block)
    keys = jnp.arange(t)

    @jax.checkpoint
    def rows(these):
        q_rows, first = these  # [B, query_block, H, hd], the first row's index
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / np.sqrt(hd)
        seen = keys[None, :] <= (first + jnp.arange(query_block))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    n = t // query_block
    out = jax.lax.map(
        rows,
        (
            jnp.moveaxis(q.reshape(batch, n, query_block, heads, hd), 1, 0),
            jnp.arange(n) * query_block,
        ),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(batch, t, heads * hd)
    return out @ blk["wo"]


# -------------------------------------------------------------- experts


def expert_choice(x, router, bias, config):
    """``(gates, chosen)``, each [tokens, router width]: the weight of
    every expert in every token's result (0 outside the token's top k)
    and which experts the top k of ``s + bias`` chose."""
    scores = jax.nn.sigmoid(x @ router)
    order = jnp.argsort(-(scores + bias), axis=-1)
    top = order[:, : config["num_experts_per_tok"]]
    weights = jnp.take_along_axis(scores, top, axis=-1)
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * config["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    gates = jnp.zeros_like(scores).at[rows, top].set(weights)
    chosen = jnp.zeros(scores.shape, bool).at[rows, top].set(True)
    return gates, chosen


def routed_experts(x, blk, bias, config, expert_ids):
    """The part of the layer's result that the experts ``expert_ids``
    give, and the loads of all the router's experts; ``blk["gate_up"]``,
    ``blk["down"]`` are stacked in that order."""
    gates, chosen = expert_choice(x, blk["router"], bias, config)
    out = jnp.zeros_like(x)
    one_expert = jax.checkpoint(_swiglu)  # recomputed in the backward pass: memory only
    for held, expert in enumerate(expert_ids):
        y = one_expert(x, blk["gate_up"][held], blk["down"][held])
        out = out + gates[:, expert, None] * y
    return out, jnp.sum(chosen, axis=0).astype(jnp.int32)


def layer(x, blk, bias, kind, config):
    """One layer; ``bias`` None for a dense one. ``(x, loads or None)``."""
    eps = config["norm_eps"]
    h = _rms_norm(x, blk["op_norm"], eps)
    x = x + (short_conv(h, blk, config) if kind == "conv" else attention(h, blk, config))
    h2 = _rms_norm(x, blk["ffn_norm"], eps)
    if bias is None:
        return x + _swiglu(h2, blk["gate_up"], blk["down"]), None
    flat = h2.reshape(-1, h2.shape[-1])
    out, loads = routed_experts(flat, blk, bias, config, config["expert_ids"])
    return x + out.reshape(h2.shape), loads


def forward(params, expert_bias, tokens, config, remat=False):
    """``(logits [B, T, V], loads)`` over the vocabulary rows held, a load
    a sparse layer. ``remat`` recomputes each layer in the backward pass
    (memory only)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(_F32), params)
        x = params["embed"][tokens]
        biases = iter(expert_bias)
        loads = []
        for (kind, sparse), blk in zip(layer_kinds(config), params["layers"]):
            bias = next(biases).astype(_F32) if sparse else None
            run = lambda x, blk, bias, kind=kind: layer(x, blk, bias, kind, config)
            x, load = (jax.checkpoint(run) if remat else run)(x, blk, bias)
            if load is not None:
                loads.append(load)
        x = _rms_norm(x, params["final_norm"], config["norm_eps"])
        return x @ params["embed"].T, loads


def loss(params, expert_bias, tokens, config, remat=False):
    """``(loss, loads)``: the next-token cross entropy of ``tokens`` [B,
    T], and the loads of the step's forward pass."""
    with jax.default_matmul_precision("highest"):
        logits, loads = forward(params, expert_bias, tokens, config, remat)
        logits = logits[:, :-1]
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(nll), loads


def update_bias(expert_bias, loads, rate):
    """The biases after a step: each expert's moved by ``rate`` towards
    the mean load, written out expert by expert."""
    out = []
    for bias, load in zip(expert_bias, loads):
        load = np.asarray(load, np.float64)
        mean = load.mean()
        moved = [
            b + rate * (1.0 if n < mean else -1.0 if n > mean else 0.0)
            for b, n in zip(np.asarray(bias, np.float64), load)
        ]
        out.append(np.asarray(moved, np.float32))
    return out
