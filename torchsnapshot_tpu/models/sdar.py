"""SDAR as a load generator: the stack of ``JetLM/SDAR-30B-A3B-Chat``
(``model_type`` ``sdar_moe``) trained by block diffusion under
mixed-precision AdamW, for one chip's share of an expert-parallel
deployment.

Like ``transformer.py``, ``nemotron_h.py`` and ``laguna.py`` this is the
load whose state is checkpointed, not the product. A layer is pre-norm
residual, ``x + attn(RMSNorm(x))`` and ``x + moe(RMSNorm(x))``, no bias
anywhere, every layer alike:

- attention. ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads; queries and keys each normed
  per head over their ``head_dim`` dimensions by an RMSNorm with a
  learned weight (``q_norm``, ``k_norm``); rotary on every dimension in
  the ``rotate_half`` form at ``rope_theta``, unscaled, **by each
  token's position id** and not by its index in the sequence; the mask
  below; through ``ops/attention.py``.
- feed-forward. A softmax router over all ``num_experts`` in float32,
  the top k, their values divided by their sum (``norm_topk_prob``),
  applied to the experts' results; SwiGLU experts of which this chip
  holds ``expert_ids`` and adds its own experts' part alone
  (``experts.py``, shared with ``nemotron_h.py`` and ``laguna.py``, as
  are the optimizer, ``mixed_adamw.py``, and the RMSNorm). No shared
  expert, no dense layer. No token is dropped.

Gate and up projections are one fused leaf, ``[held, d, 2 f]``, gate
first. Untied embedding and head over the vocabulary rows held here, a
final RMSNorm.

**The objective** (block diffusion; :func:`noise`, :func:`loss_fn`). A
sequence ``x0`` of ``L`` tokens lies in blocks of ``block_length``.
From the step's key a time ``t`` is drawn a block, uniform on (0, 1)
and clipped to [1e-3, 1], and each token of the block is replaced by
the mask id with probability ``t``: ``xt``. The model sees ``2 L``
tokens, **the clean half ``x0`` first and the noised half ``xt`` after
it**, both with position ids ``0 .. L-1``. A clean query sees the clean
keys of its own block and of the blocks before it; a noised query sees
the clean keys of the blocks strictly before its own and the noised
keys of its own block; a clean query never sees a noised key
(``flash_attention(block_diffusion=(L, block_length))``). The loss is
``(1 / L) sum over masked i of (1 / t_i) * cross-entropy(logits of
noised position i, x0[i])``, labels unshifted, over the vocabulary rows
held, the mean over the batch. The mask id is the last row held and is
never drawn as a token. The training state is ``mixed_adamw``'s: 16
bytes a parameter resident, 14 saved.
"""

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import (
    block_diffusion_mask,
    flash_attention,
    resolve_flash_block,
)
from . import experts
from .mixed_adamw import AdamW, adamw_update, state_of_master
from .nemotron_h import rms_norm

_F32 = jnp.float32
_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
T_MIN = 1e-3  # a block's time is clipped to [T_MIN, 1]


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """Published sizes by default; ``layers``, ``expert_ids`` and
    ``vocab_size`` are what one chip of the deployment holds."""

    hidden_size: int = 2048
    layers: int = 6
    vocab_size: int = 18992
    rms_norm_eps: float = 1e-6
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1000000.0
    flash_attention: bool = True
    # feed-forward
    num_experts: int = 128  # the router's width
    expert_ids: Tuple[int, ...] = tuple(range(16))
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    expert_capacity: int = 0  # see experts.Routing.capacity
    expert_dense_group: int = 0  # see experts.Routing.dense_group
    # the objective
    block_length: int = 4
    # numerics
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def mask_token_id(self) -> int:
        return self.vocab_size - 1

    @property
    def routing(self) -> experts.Routing:
        return experts.Routing(
            expert_ids=self.expert_ids,
            top_k=self.num_experts_per_tok,
            normalise=self.norm_topk_prob,
            scaling_factor=1.0,
            capacity=self.expert_capacity,
            dense_group=self.expert_dense_group,
            scoring="softmax",
        )


def config_from_published(config: Dict[str, Any]) -> SdarConfig:
    """The program's configuration from a configuration file's keys:
    the published ones of ``config.json`` (``num_experts`` the experts
    held, their ids under ``expert_ids``, the router's width under
    ``published``; ``vocab_size`` the rows held; ``layers_held`` of
    ``num_hidden_layers``) and the job's ``block_length``, ``attention``,
    ``expert_capacity``, ``expert_dense_group``, ``param_dtype``,
    ``remat``. What the model cannot run is refused aloud."""
    if config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("models/sdar.py has no bias and an untied head")
    if config["hidden_act"] != "silu" or config["rope_scaling"] is not None:
        raise ValueError("models/sdar.py has SwiGLU experts and unscaled rotary")
    if config["use_sliding_window"] or config["sliding_window"] is not None:
        raise ValueError("models/sdar.py has no sliding window")
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError("models/sdar.py has a sparse feed-forward in every layer")
    if len(config["expert_ids"]) != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: one id each")
    return SdarConfig(
        hidden_size=config["hidden_size"],
        layers=config["layers_held"],
        vocab_size=config["vocab_size"],
        rms_norm_eps=config["rms_norm_eps"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        flash_attention=config["attention"] == "flash",
        num_experts=config["published"]["num_experts"],
        expert_ids=tuple(config["expert_ids"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        expert_capacity=config.get("expert_capacity", 0),
        expert_dense_group=config.get("expert_dense_group", 0),
        block_length=config["block_length"],
        dtype=_DTYPES[config["param_dtype"]],
        remat=config.get("remat", True),
    )


# ------------------------------------------------------------------ init


def init_master(config: SdarConfig, key: jax.Array) -> Dict[str, Any]:
    """Float32 parameters from a key: normal(0, 0.02) matrices, norms 1."""
    d, hd = config.hidden_size, config.head_dim
    keys = iter(jax.random.split(key, 7 * config.layers + 2))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, _F32) * 0.02

    def layer():
        kv = config.num_key_value_heads * hd
        held, f = len(config.expert_ids), config.moe_intermediate_size
        return {
            "attn_norm": jnp.ones((d,), _F32),
            "wq": dense(d, config.num_attention_heads * hd),
            "wk": dense(d, kv),
            "wv": dense(d, kv),
            "q_norm": jnp.ones((hd,), _F32),
            "k_norm": jnp.ones((hd,), _F32),
            "wo": dense(config.num_attention_heads * hd, d),
            "mlp_norm": jnp.ones((d,), _F32),
            "router": dense(d, config.num_experts),
            "gate_up": dense(held, d, 2 * f),
            "down": dense(held, f, d),
        }

    return {
        "embed": dense(config.vocab_size, d),
        "layers": [layer() for _ in range(config.layers)],
        "final_norm": jnp.ones((d,), _F32),
        "head": dense(d, config.vocab_size),
    }


def init_state(config: SdarConfig, key: jax.Array) -> Dict[str, Any]:
    """The whole training state, jit-able (``mixed_adamw``)."""
    return state_of_master(init_master(config, key), config.dtype)


# ------------------------------------------------------------- objective


def draw_tokens(key, shape, config: SdarConfig):
    """Token ids from the rows held, never the mask id."""
    return jax.random.randint(key, shape, 0, config.mask_token_id)


def noise(tokens, key, config: SdarConfig):
    """``(xt, masked, t)`` of ``tokens`` [B, L]: a time a block of
    ``block_length`` tokens, uniform and clipped to [T_MIN, 1]; each
    token masked with its block's probability. A function of the key
    alone."""
    batch, length = tokens.shape
    if length % config.block_length:
        raise ValueError(
            f"{length} tokens are no whole number of blocks of {config.block_length}"
        )
    time_key, mask_key = jax.random.split(key)
    t = jnp.clip(
        jax.random.uniform(time_key, (batch, length // config.block_length), _F32),
        T_MIN, 1.0,
    )
    t = jnp.repeat(t, config.block_length, axis=1)
    masked = jax.random.uniform(mask_key, (batch, length), _F32) < t
    return jnp.where(masked, config.mask_token_id, tokens), masked, t


# ---------------------------------------------------------------- blocks


def apply_rope(x, positions, theta: float):
    """Rotary on every dimension of each head of ``x`` [B, T, H,
    head_dim] by ``positions`` [T] (position ids, not indices),
    ``rotate_half`` form, in float32. Result in ``x``'s dtype."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angles = positions.astype(_F32)[:, None] * jnp.asarray(inv_freq)
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x32 = x.astype(_F32)
    first, second = x32[..., :half], x32[..., half:]
    rotated = jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def _swiglu(h):
    """``silu(gate) * up`` of a fused ``[..., 2 f]`` projection."""
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


def attention(h, blk, positions, config: SdarConfig):
    """One layer's attention on its normed input ``h`` [B, 2 L, d], the
    clean half first."""
    batch, t, _ = h.shape
    heads, kv, hd = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    eps = config.rms_norm_eps
    q = jnp.einsum("btd,dh->bth", h, blk["wq"]).reshape(batch, t, heads, hd)
    k = jnp.einsum("btd,dh->bth", h, blk["wk"]).reshape(batch, t, kv, hd)
    v = jnp.einsum("btd,dh->bth", h, blk["wv"]).reshape(batch, t, kv, hd)
    q = apply_rope(rms_norm(q, blk["q_norm"], eps), positions, config.rope_theta)
    k = apply_rope(rms_norm(k, blk["k_norm"], eps), positions, config.rope_theta)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    diffusion = (t // 2, config.block_length)
    if config.flash_attention:
        tile = resolve_flash_block(t)
        out = flash_attention(
            q, k, v, block_q=tile, block_k=tile, block_diffusion=diffusion
        )
    else:
        k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=_F32
        ) / np.sqrt(hd)
        scores = jnp.where(block_diffusion_mask(*diffusion), scores, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores).astype(h.dtype), v)
    out = out.transpose(0, 2, 1, 3).reshape(batch, t, heads * hd)
    return jnp.einsum("bth,hd->btd", out, blk["wo"])


def routed_experts(x, blk, config: SdarConfig):
    """The held experts' part of the layer's result, ``x`` [tokens, d]."""
    return experts.routed_experts(
        x, blk["router"], None, lambda project: _swiglu(project(blk["gate_up"])),
        blk["down"], config.routing,
    )


def layer(x, blk, positions, config: SdarConfig):
    eps = config.rms_norm_eps
    x = x + attention(rms_norm(x, blk["attn_norm"], eps), blk, positions, config)
    h = rms_norm(x, blk["mlp_norm"], eps)
    return x + routed_experts(h.reshape(-1, h.shape[-1]), blk, config).reshape(h.shape)


# -------------------------------------------------------- model and step


def hidden(params, x0, xt, config: SdarConfig):
    """The final-normed residual stream [B, 2 L, d] of the clean tokens
    ``x0`` and, after them, the noised ``xt``, both [B, L]."""
    length = x0.shape[1]
    positions = jnp.tile(jnp.arange(length), 2)
    x = params["embed"][jnp.concatenate([x0, xt], axis=1)]
    for blk in params["layers"]:
        run = lambda x, blk: layer(x, blk, positions, config)
        x = (jax.checkpoint(run) if config.remat else run)(x, blk)
    return rms_norm(x, params["final_norm"], config.rms_norm_eps)


def _head(x, params):
    return jnp.einsum("btd,dv->btv", x, params["head"], preferred_element_type=_F32)


def forward(params, x0, xt, config: SdarConfig):
    """Logits [B, 2 L, V] in float32 over the vocabulary rows held here:
    the clean half's, then the noised half's."""
    return _head(hidden(params, x0, xt, config), params)


def loss_fn(params, tokens, key, config: SdarConfig):
    """The block-diffusion loss of ``tokens`` [B, L] under the noise of
    ``key``. The head runs on the noised half alone: no other logit
    enters the loss."""
    xt, masked, t = noise(tokens, key, config)
    length = tokens.shape[1]
    logits = _head(hidden(params, tokens, xt, config)[:, length:], params)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, :, None], axis=-1)[..., 0]
    return -jnp.mean(jnp.sum(jnp.where(masked, picked / t, 0.0), axis=1) / length)


def adamw_train_step(state, tokens, key, config: SdarConfig, hp: AdamW = AdamW()):
    """One step; gradients in the compute dtype. Returns (state, loss)."""
    loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens, key, config)
    return adamw_update(state, grads, hp), loss
