"""The plain reference of the ``sdar_moe`` stack and its block-diffusion
objective: noising, forward, loss and gradients in straightforward
float32 ``jax.numpy``, the benchmark's own copy, importing nothing of
the program.

It follows https://huggingface.co/JetLM/SDAR-30B-A3B-Chat
(``config.json``, ``model_type`` ``sdar_moe``; JetLM, "SDAR: A
Synergistic Diffusion-AutoRegression Paradigm for Scalable Sequence
Generation", 2025) layer by layer; one layer, on ``x`` [B, T, d], eps
``rms_norm_eps``, no bias anywhere:

- ``h = rmsnorm(x)``; ``q = h Wq`` (``num_attention_heads`` heads),
  ``k = h Wk``, ``v = h Wv`` (``num_key_value_heads`` heads); ``q`` and
  ``k`` each normed per head over ``head_dim`` by an RMS norm with a
  learned weight (``q_norm``, ``k_norm``).
- Rotary on all ``head_dim`` dimensions of q and k, ``x cos +
  rotate_half(x) sin``, ``theta ** (-2 i / head_dim)``, unscaled, by
  each token's **position id**.
- ``s = q k^T / sqrt(head_dim)`` under the mask below, the queries a
  block at a time so that a long sequence fits; ``o = softmax(s) v``;
  query head a reads key/value head ``a // (heads / kv heads)``;
  ``x <- x + concat(o) Wo``.
- ``h2 = rmsnorm(x)``; ``p = softmax(h2 Wr)`` over all ``num_experts``;
  the ``num_experts_per_tok`` largest; their values divided by their
  sum (``norm_topk_prob``); the experts as a Python loop over the ids
  given, each ``(silu(h2 Wg) * h2 Wu) Wd`` on every token and weighted
  by its gate. Given all ids it is the uncut layer; given a chip's
  share, that share's part. No shared expert.

Embedding and untied head over the rows held, a final rmsnorm.

The objective. ``x0`` [B, L] lies in blocks of ``block_length``, token i
in block ``i // block_length``. From the step's key: ``t`` a block,
uniform on (0, 1), clipped to [1e-3, 1]; token i masked with probability
``t`` of its block; ``xt[i]`` the mask id (the last row held) where
masked, else ``x0[i]``. The model sees the ``2 L`` tokens ``x0`` then
``xt``, both with position ids ``0 .. L-1``; every token carries
(noised?, block), and query i sees key j iff

- both noised and ``block(i) == block(j)``; or
- i noised, j clean and ``block(j) < block(i)``; or
- both clean and ``block(j) <= block(i)``

(so a clean query never sees a noised key), written out in
:func:`sees` over those two per-token arrays. ``loss = mean over the
batch of (1 / L) sum over masked i of (1 / t_i) * cross-entropy(logits
of noised position i, x0[i])``, labels unshifted.

Everything runs under ``jax.default_matmul_precision("highest")``.
``config`` is a plain dict with the published keys of ``config.json``,
of which ``num_experts`` is the router's width, ``expert_ids`` and
``vocab_size`` are what is held, plus ``block_length``. ``params`` is
the program's parameter tree (gate and up projections fused in one
leaf, gate first); every leaf is cast to float32 first, so the
reference sees the values the program computes with.

Departures from the source: none that the published config decides
(each expert of the loop and each block of queries is recomputed in the
backward pass: memory, not arithmetic). What it leaves open is listed
under ``assumed`` in the configuration's file (block length, schedule
and weight, a time a block, unshifted labels, the mask id, the
query/key norms).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
T_MIN = 1e-3


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _swiglu(x, gate_up, down):
    f = gate_up.shape[-1] // 2
    return (_silu(x @ gate_up[:, :f]) * (x @ gate_up[:, f:])) @ down


# ------------------------------------------------------------- objective


def noise(tokens, key, config):
    """``(xt, masked, t)``, each [B, L]."""
    batch, length = tokens.shape
    block = config["block_length"]
    time_key, mask_key = jax.random.split(key)
    t_of_block = jnp.clip(
        jax.random.uniform(time_key, (batch, length // block), _F32), T_MIN, 1.0
    )
    t = t_of_block[:, jnp.arange(length) // block]
    masked = jax.random.uniform(mask_key, (batch, length), _F32) < t
    mask_id = config["vocab_size"] - 1
    return jnp.where(masked, mask_id, tokens), masked, t


def doubled(length, block):
    """What each of the ``2 L`` tokens carries: its position id, whether
    it is of the noised half, its block. The clean half comes first."""
    position = np.concatenate([np.arange(length), np.arange(length)])
    noised = np.concatenate([np.zeros(length, bool), np.ones(length, bool)])
    return position, noised, position // block


def sees(q_noised, q_block, k_noised, k_block):
    """The predicate, on arrays that broadcast: query against key."""
    both_noised = q_noised & k_noised & (q_block == k_block)
    noised_on_clean = q_noised & ~k_noised & (k_block < q_block)
    both_clean = ~q_noised & ~k_noised & (k_block <= q_block)
    return both_noised | noised_on_clean | both_clean


# ----------------------------------------------------------------- layer


def rotary_tables(theta, head_dim, positions):
    """``cos``, ``sin`` [T, head_dim], each the half-table twice."""
    inv_freq = np.asarray(
        [theta ** (-2 * i / head_dim) for i in range(head_dim // 2)], np.float32
    )
    freqs = jnp.asarray(positions, _F32)[:, None] * jnp.asarray(inv_freq)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def apply_rotary(x, cos, sin):
    """``x`` [B, T, H, head_dim]: ``x cos + rotate_half(x) sin``."""
    half = x.shape[-1] // 2
    rotate_half = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[None, :, None, :] + rotate_half * sin[None, :, None, :]


def attention(h, blk, config, query_block=None):
    """``h`` [B, 2 L, d]. ``query_block`` (or the config's, or 1024):
    the queries whose scores exist at once (memory, not arithmetic)."""
    query_block = query_block or config.get("query_block", 1024)
    heads, kv, hd = (
        config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"],
    )
    eps = config["rms_norm_eps"]
    batch, t, _ = h.shape
    position, noised, block = doubled(t // 2, config["block_length"])
    cos, sin = rotary_tables(config["rope_theta"], hd, position)
    q = _rms_norm((h @ blk["wq"]).reshape(batch, t, heads, hd), blk["q_norm"], eps)
    k = _rms_norm((h @ blk["wk"]).reshape(batch, t, kv, hd), blk["k_norm"], eps)
    q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
    v = (h @ blk["wv"]).reshape(batch, t, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=2)
    v = jnp.repeat(v, heads // kv, axis=2)
    noised, block = jnp.asarray(noised), jnp.asarray(block)
    query_block = math.gcd(t, query_block)

    @jax.checkpoint
    def rows(these):
        q_rows, q_noised, q_block = these  # [B, query_block, H, hd], [query_block] x 2
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / np.sqrt(hd)
        seen = sees(q_noised[:, None], q_block[:, None], noised[None, :], block[None, :])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    n = t // query_block
    out = jax.lax.map(
        rows,
        (
            jnp.moveaxis(q.reshape(batch, n, query_block, heads, hd), 1, 0),
            noised.reshape(n, query_block),
            block.reshape(n, query_block),
        ),
    )
    out = jnp.moveaxis(out, 0, 1).reshape(batch, t, heads * hd)
    return out @ blk["wo"]


def expert_gates(x, blk, config):
    """[tokens, num_experts]: the weight of every expert in every token's
    result, 0 outside the token's top k."""
    scores = jax.nn.softmax(x @ blk["router"], axis=-1)
    order = jnp.argsort(-scores, axis=-1)
    chosen = order[:, : config["num_experts_per_tok"]]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if config.get("norm_topk_prob", True):
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
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


def layer(x, blk, config):
    eps = config["rms_norm_eps"]
    x = x + attention(_rms_norm(x, blk["attn_norm"], eps), blk, config)
    h = _rms_norm(x, blk["mlp_norm"], eps)
    flat = h.reshape(-1, h.shape[-1])
    return x + routed_experts(flat, blk, config, config["expert_ids"]).reshape(h.shape)


def forward(params, x0, xt, config, remat=False):
    """Logits [B, 2 L, V] over the vocabulary rows held: the clean half's,
    then the noised half's. ``remat`` recomputes each layer in the
    backward pass (memory only)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(_F32), params)
        x = params["embed"][jnp.concatenate([x0, xt], axis=1)]
        for blk in params["layers"]:
            run = lambda x, blk: layer(x, blk, config)
            x = (jax.checkpoint(run) if remat else run)(x, blk)
        x = _rms_norm(x, params["final_norm"], config["rms_norm_eps"])
        return x @ params["head"]


def loss(params, tokens, key, config, remat=False):
    """The block-diffusion loss of ``tokens`` [B, L] under ``key``'s noise."""
    with jax.default_matmul_precision("highest"):
        xt, masked, t = noise(tokens, key, config)
        length = tokens.shape[1]
        logits = forward(params, tokens, xt, config, remat)[:, length:]
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, tokens[:, :, None], axis=-1)[..., 0]
        per_sequence = jnp.sum(jnp.where(masked, nll / t, 0.0), axis=1) / length
        return jnp.mean(per_sequence)
