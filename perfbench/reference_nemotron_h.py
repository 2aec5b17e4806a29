"""The plain reference of the ``nemotron_h`` stack: forward, loss and
gradients in straightforward float32 ``jax.numpy``, the benchmark's own
copy, importing nothing of the program.

It follows ``modeling_nemotron_h.py`` of
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 block
by block (pre-norm residual, one mixer a block):

- ``M``: the Mamba-2 recurrence written out step by step (a scan over
  time with the state as the carry), no chunks, no duality;
- ``E``: sigmoid router over all experts, top k by score + correction
  bias, the chosen scores normalised and scaled; the experts as a Python
  loop over the ids given (``relu(x W_up)^2 W_down``), each applied to
  every token and weighted by its gate, plus the shared expert. Given
  all ids it is the uncut layer; given a chip's share, that share's part;
- ``*``: causal grouped-query attention by materialised scores, no
  positional embedding, the queries taken a block at a time so that the
  scores of a long sequence fit.

Everything runs under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matrix product is otherwise computed in bfloat16 passes.
``config`` is a plain dict with the published keys of the model's
``config.json`` plus ``pattern`` (the blocks held), ``expert_ids`` and
``vocab_size`` (the rows held). ``params`` is the program's parameter
tree; every leaf is cast to float32 first, so the reference sees the
values the program computes with.

Departures from the source: none in the mathematics. The time scan is
cut into segments that are recomputed in the backward pass (memory, not
arithmetic), and ``time_step_limit`` defaults to the source's (0, inf).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


def _rms_norm(x, weight, eps, group_size=None):
    shape = x.shape
    if group_size is not None:
        x = x.reshape(*shape[:-1], -1, group_size)
    x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * weight


def _relu2(x):
    return jnp.maximum(x, 0.0) ** 2


def mamba_recurrence(x, dt, a, b, c):
    """``h_t = exp(dt_t a) h_{t-1} + dt_t b_t x_t^T``; ``y_t = c_t h_t``,
    one time step after another. ``x`` [B, T, H, P], ``dt`` [B, T, H],
    ``a`` [H], ``b``, ``c`` [B, T, H, N] (already one per head)."""
    batch, t, heads, p = x.shape
    n = b.shape[-1]
    segment = math.gcd(t, 128) if math.gcd(t, 128) >= 8 else t

    def one_step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, H, P], [B, H], [B, H, N], [B, H, N]
        h = h * jnp.exp(dt_t * a)[..., None, None] + (
            (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        )
        return h, jnp.einsum("bhpn,bhn->bhp", h, c_t)

    @jax.checkpoint
    def one_segment(h, inputs):
        return jax.lax.scan(one_step, h, inputs)

    def by_segment(v):
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(t // segment, segment, *v.shape[1:])

    _, y = jax.lax.scan(
        one_segment,
        jnp.zeros((batch, heads, p, n), _F32),
        tuple(by_segment(v) for v in (x, dt, b, c)),
    )
    return jnp.moveaxis(y.reshape(t, batch, heads, p), 0, 1)


def mamba_mixer(x, blk, config):
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    inner = heads * p
    k = config["conv_kernel"]
    batch, t, _ = x.shape
    proj = x @ blk["in_proj"]
    z = proj[..., :inner]
    xbc = proj[..., inner : inner + inner + 2 * groups * n]
    dt = proj[..., inner + inner + 2 * groups * n :]
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    conv = blk["conv_b"] + sum(
        padded[:, j : j + t] * blk["conv_w"][j] for j in range(k)
    )
    xbc = conv * jax.nn.sigmoid(conv)  # silu
    xs = xbc[..., :inner].reshape(batch, t, heads, p)
    b = xbc[..., inner : inner + groups * n].reshape(batch, t, groups, n)
    c = xbc[..., inner + groups * n :].reshape(batch, t, groups, n)
    b = jnp.repeat(b, heads // groups, axis=2)  # head h reads group h // (H / G)
    c = jnp.repeat(c, heads // groups, axis=2)
    low, high = config.get("time_step_limit", (0.0, math.inf))
    dt = jnp.clip(jax.nn.softplus(dt + blk["dt_bias"]), low, high)
    y = mamba_recurrence(xs, dt, -jnp.exp(blk["A_log"]), b, c)
    y = y + xs * blk["D"][:, None]
    y = y.reshape(batch, t, inner) * (z * jax.nn.sigmoid(z))
    y = _rms_norm(y, blk["gate_norm"], config["norm_eps"], inner // groups)
    return y @ blk["out_proj"]


def expert_gates(x, blk, config):
    """[tokens, n_routed_experts]: the weight of every expert in every
    token's result, 0 outside the token's top k."""
    scores = jax.nn.sigmoid(x @ blk["router"])
    order = jnp.argsort(-(scores + blk["router_bias"]), axis=-1)
    chosen = order[:, : config["num_experts_per_tok"]]
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * config["routed_scaling_factor"]
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(weights)


def routed_experts(x, blk, config, expert_ids):
    """The part of the layer's result that the experts ``expert_ids``
    give; ``blk["up"]``, ``blk["down"]`` are stacked in that order."""
    gates = expert_gates(x, blk, config)
    out = jnp.zeros_like(x)
    for held, expert in enumerate(expert_ids):
        y = _relu2(x @ blk["up"][held]) @ blk["down"][held]
        out = out + gates[:, expert, None] * y
    return out


def shared_expert(x, blk):
    return _relu2(x @ blk["shared_up"]) @ blk["shared_down"]


def moe_mixer(x, blk, config):
    flat = x.reshape(-1, x.shape[-1])
    out = routed_experts(flat, blk, config, config["expert_ids"]) + shared_expert(
        flat, blk
    )
    return out.reshape(x.shape)


def attention_mixer(x, blk, config, query_block=1024):
    heads, kv, hd = (
        config["num_attention_heads"],
        config["num_key_value_heads"],
        config["head_dim"],
    )
    batch, t, _ = x.shape
    q = (x @ blk["wq"]).reshape(batch, t, heads, hd)
    k = jnp.repeat((x @ blk["wk"]).reshape(batch, t, kv, hd), heads // kv, axis=2)
    v = jnp.repeat((x @ blk["wv"]).reshape(batch, t, kv, hd), heads // kv, axis=2)

    @jax.checkpoint
    def rows(q_rows, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / np.sqrt(hd)
        q_pos = first + jnp.arange(q_rows.shape[1])
        scores = jnp.where(
            q_pos[:, None] >= jnp.arange(t)[None, :], scores, -jnp.inf
        )
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    out = jnp.concatenate(
        [
            rows(q[:, first : first + query_block], first)
            for first in range(0, t, query_block)
        ],
        axis=1,
    )
    return out.reshape(batch, t, heads * hd) @ blk["wo"]


_MIXERS = {"M": mamba_mixer, "E": moe_mixer, "*": attention_mixer}


def block(x, blk, kind, config):
    return x + _MIXERS[kind](_rms_norm(x, blk["norm"], config["norm_eps"]), blk, config)


def forward(params, tokens, config, remat=False):
    """Logits [B, T, V] over the vocabulary rows held. ``remat``
    recomputes each block in the backward pass (memory only)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda p: p.astype(_F32), params)
        x = params["embed"][tokens]
        for kind, blk in zip(config["pattern"], params["blocks"]):
            run = lambda x, blk, kind=kind: block(x, blk, kind, config)
            x = (jax.checkpoint(run) if remat else run)(x, blk)
        x = _rms_norm(x, params["final_norm"], config["norm_eps"])
        return x @ params["head"]


def loss(params, tokens, config, remat=False):
    """Next-token cross entropy over the held rows."""
    with jax.default_matmul_precision("highest"):
        logits = forward(params, tokens, config, remat)[:, :-1]
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(picked)


def adamw_step_numpy(master, mu, nu, count, grad, hp):
    """One AdamW step of one leaf, written out in numpy float32: returns
    (master, mu, nu, count). ``hp``: ``lr``, ``b1``, ``b2``, ``eps``,
    ``weight_decay`` (decoupled; on leaves of two or more axes)."""
    f32 = np.float32
    grad = np.asarray(grad, f32)
    count = count + 1
    mu = f32(hp["b1"]) * mu + f32(1 - hp["b1"]) * grad
    nu = f32(hp["b2"]) * nu + f32(1 - hp["b2"]) * grad * grad
    mu_hat = mu / f32(1 - hp["b1"] ** count)
    nu_hat = nu / f32(1 - hp["b2"] ** count)
    update = mu_hat / (np.sqrt(nu_hat) + f32(hp["eps"]))
    if master.ndim >= 2:
        update = update + f32(hp["weight_decay"]) * master
    return (master - f32(hp["lr"]) * update).astype(f32), mu, nu, count
