"""Laguna as a load generator: the stack of ``poolside/Laguna-XS.2``
(``model_type`` ``laguna``) under mixed-precision AdamW, for one chip's
share of an expert-parallel deployment.

Like ``transformer.py`` and ``nemotron_h.py`` this is the load whose
state is checkpointed, not the product. A layer is pre-norm residual,
attention then feed-forward, ``x + attn(RMSNorm(x))`` and ``x +
mlp(RMSNorm(x))``, no bias anywhere, and layers differ by kind:

- attention, ``layer_types[l]``. ``H = num_attention_heads_per_layer[l]``
  query heads over ``num_key_value_heads`` key/value heads, so a
  layer's leaves differ in shape by kind. ``full_attention``: causal,
  rotary on the first ``partial_rotary_factor`` of each head's
  dimensions with YaRN frequencies, cos and sin scaled by its
  ``attention_factor``. ``sliding_attention``: query i sees the keys j
  with ``0 <= i - j < sliding_window``, plain rotary on every dimension
  with a base of its own. Both through ``ops/attention.py``. A per-head
  output gate: head a of the result times ``sigmoid(h Wg)[:, a]``, from
  the layer's normed input ``h``, before the output projection.
- feed-forward, ``mlp_layer_types[l]``. ``dense``: SwiGLU, ``(silu(x
  W_gate) * x W_up) W_down``. ``sparse``: a sigmoid router over all
  ``num_experts``, the top k, their scores divided by their sum and
  times ``moe_routed_scaling_factor``, applied to the experts' results;
  SwiGLU experts of which this chip holds ``expert_ids`` and adds its
  own experts' part alone (``experts.py``, shared with
  ``nemotron_h.py``, as are the optimizer, ``mixed_adamw.py``, and the
  RMSNorm); plus one shared SwiGLU expert on every token. No token is
  dropped.

Gate and up projections are one fused leaf each, ``[.., d, 2 f]``, gate
first. Untied embedding and head over the vocabulary rows held here, a
final RMSNorm, next-token cross entropy over the held rows. The training
state is ``mixed_adamw``'s: 16 bytes a parameter resident, 14 saved.

Rotary is the source family's ``rotate_half`` form (a head's rotated
dimensions as two halves, not interleaved pairs); YaRN's frequencies are
computed as ``transformers`` computes them
(``_compute_yarn_parameters``), in numpy at trace time.
"""

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import flash_attention, resolve_flash_block
from . import experts
from .mixed_adamw import AdamW, adamw_update, state_of_master
from .nemotron_h import rms_norm

_F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class Rope:
    """One attention kind's rotary embedding. ``factor`` None: plain
    rotary; otherwise YaRN with these parameters."""

    theta: float
    partial_rotary_factor: float = 1.0
    factor: Optional[float] = None
    original_max_position_embeddings: Optional[int] = None
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Published sizes by default; the three per-layer tuples (of equal
    length: the layers held), ``expert_ids`` and ``vocab_size`` are what
    one chip of the deployment holds."""

    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    mlp_layer_types: Tuple[str, ...] = (DENSE, SPARSE, SPARSE, SPARSE, SPARSE)
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64, 48)
    vocab_size: int = 12544
    rms_norm_eps: float = 1e-6
    # attention
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    max_position_embeddings: int = 262144
    rope_full: Rope = Rope(
        theta=500000.0,
        partial_rotary_factor=0.5,
        factor=64.0,
        original_max_position_embeddings=4096,
        beta_fast=64.0,
        beta_slow=1.0,
        attention_factor=1.4158883083359672,
    )
    rope_sliding: Rope = Rope(theta=10000.0)
    flash_attention: bool = True
    # feed-forward
    intermediate_size: int = 8192
    num_experts: int = 256  # the router's width
    expert_ids: Tuple[int, ...] = tuple(range(32))
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    expert_capacity: int = 0  # see experts.Routing.capacity
    expert_dense_group: int = 0  # see experts.Routing.dense_group
    # numerics
    dtype: Any = jnp.bfloat16
    remat: bool = True

    def __post_init__(self):
        held = len(self.layer_types)
        if not (
            len(self.mlp_layer_types) == len(self.num_attention_heads_per_layer) == held
        ):
            raise ValueError("one entry a held layer in each per-layer tuple")

    @property
    def routing(self) -> experts.Routing:
        return experts.Routing(
            expert_ids=self.expert_ids,
            top_k=self.num_experts_per_tok,
            normalise=True,
            scaling_factor=self.moe_routed_scaling_factor,
            capacity=self.expert_capacity,
            dense_group=self.expert_dense_group,
        )


# ------------------------------------------------------------------ init


def init_master(config: LagunaConfig, key: jax.Array) -> Dict[str, Any]:
    """Float32 parameters from a key: normal(0, 0.02) matrices, norms 1."""
    d, hd = config.hidden_size, config.head_dim
    keys = iter(jax.random.split(key, 12 * len(config.layer_types) + 2))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, _F32) * 0.02

    def layer(heads, mlp):
        kv = config.num_key_value_heads * hd
        blk = {
            "attn_norm": jnp.ones((d,), _F32),
            "wq": dense(d, heads * hd),
            "wk": dense(d, kv),
            "wv": dense(d, kv),
            "wg": dense(d, heads),
            "wo": dense(heads * hd, d),
            "mlp_norm": jnp.ones((d,), _F32),
        }
        if mlp == DENSE:
            f = config.intermediate_size
            return dict(blk, gate_up=dense(d, 2 * f), down=dense(f, d))
        held, f = len(config.expert_ids), config.moe_intermediate_size
        fs = config.shared_expert_intermediate_size
        return dict(
            blk,
            router=dense(d, config.num_experts),
            gate_up=dense(held, d, 2 * f),
            down=dense(held, f, d),
            shared_gate_up=dense(d, 2 * fs),
            shared_down=dense(fs, d),
        )

    return {
        "embed": dense(config.vocab_size, d),
        "layers": [
            layer(heads, mlp)
            for heads, mlp in zip(
                config.num_attention_heads_per_layer, config.mlp_layer_types
            )
        ],
        "final_norm": jnp.ones((d,), _F32),
        "head": dense(d, config.vocab_size),
    }


def init_state(config: LagunaConfig, key: jax.Array) -> Dict[str, Any]:
    """The whole training state, jit-able (``mixed_adamw``)."""
    return state_of_master(init_master(config, key), config.dtype)


# ---------------------------------------------------------------- rotary


def rope_inv_freq(rope: Rope, head_dim: int, max_position_embeddings: int):
    """``(inv_freq [rotary_dim / 2] float32, attention_factor)``: plain
    rotary's ``theta ** (-2 i / dim)``, or YaRN's blend of those
    (extrapolation, dimensions that turn often within the original
    context) with the same divided by ``factor`` (interpolation), by a
    linear ramp between the dimensions that make ``beta_fast`` and
    ``beta_slow`` turns over the original context. As
    ``transformers._compute_yarn_parameters``."""
    dim = int(head_dim * rope.partial_rotary_factor)
    pos_freqs = rope.theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if rope.factor is None:
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    original = rope.original_max_position_embeddings
    factor = max_position_embeddings / original if original else rope.factor
    original = original or max_position_embeddings
    attention_factor = rope.attention_factor
    if attention_factor is None:
        attention_factor = 1.0 if factor <= 1 else 0.1 * math.log(factor) + 1.0

    def correction_dim(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))) / (
            2 * math.log(rope.theta)
        )

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1
    )
    extrapolation = 1.0 - ramp
    inv_freq = (1.0 / (factor * pos_freqs)) * (1 - extrapolation) + (
        1.0 / pos_freqs
    ) * extrapolation
    return inv_freq.astype(np.float32), float(attention_factor)


def apply_rope(x, inv_freq, attention_factor):
    """Rotary on the first ``2 * len(inv_freq)`` dimensions of each head
    of ``x`` [B, T, H, head_dim], positions 0..T-1, in float32; the rest
    pass. Result in ``x``'s dtype."""
    rot = 2 * inv_freq.shape[0]
    angles = jnp.arange(x.shape[1], dtype=_F32)[:, None] * jnp.asarray(inv_freq)
    cos = jnp.cos(angles)[None, :, None, :] * attention_factor
    sin = jnp.sin(angles)[None, :, None, :] * attention_factor
    x32 = x.astype(_F32)
    first, second = x32[..., : rot // 2], x32[..., rot // 2 : rot]
    rotated = jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin, x32[..., rot:]],
        axis=-1,
    )
    return rotated.astype(x.dtype)


# ---------------------------------------------------------------- blocks


def _swiglu(h):
    """``silu(gate) * up`` of a fused ``[..., 2 f]`` projection."""
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


def attention(h, blk, kind: str, config: LagunaConfig):
    """One layer's attention on its normed input ``h`` [B, T, d]."""
    batch, t, _ = h.shape
    kv, hd = config.num_key_value_heads, config.head_dim
    heads = blk["wg"].shape[1]
    window = config.sliding_window if kind == SLIDING else None
    rope = config.rope_sliding if kind == SLIDING else config.rope_full
    inv_freq, factor = rope_inv_freq(rope, hd, config.max_position_embeddings)
    q = jnp.einsum("btd,dh->bth", h, blk["wq"]).reshape(batch, t, heads, hd)
    k = jnp.einsum("btd,dh->bth", h, blk["wk"]).reshape(batch, t, kv, hd)
    v = jnp.einsum("btd,dh->bth", h, blk["wv"]).reshape(batch, t, kv, hd)
    q, k = apply_rope(q, inv_freq, factor), apply_rope(k, inv_freq, factor)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    if config.flash_attention:
        block = resolve_flash_block(t)
        out = flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block, window=window
        )
    else:
        k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=_F32
        ) / np.sqrt(hd)
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = (j <= i) if window is None else (j <= i) & (i - j < window)
        scores = jnp.where(seen, scores, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores).astype(h.dtype), v)
    gate = jax.nn.sigmoid(
        jnp.einsum("btd,dh->bth", h, blk["wg"], preferred_element_type=_F32)
    )
    out = out.transpose(0, 2, 1, 3) * gate[..., None].astype(out.dtype)
    return jnp.einsum("bth,hd->btd", out.reshape(batch, t, heads * hd), blk["wo"])


def dense_mlp(h, blk):
    return jnp.einsum(
        "btf,fd->btd", _swiglu(jnp.einsum("btd,df->btf", h, blk["gate_up"])), blk["down"]
    )


def routed_experts(x, blk, config: LagunaConfig):
    """The held experts' part of the layer's result, ``x`` [tokens, d]."""
    return experts.routed_experts(
        x, blk["router"], None, lambda project: _swiglu(project(blk["gate_up"])),
        blk["down"], config.routing,
    )


def shared_expert(x, blk):
    return jnp.einsum(
        "tf,fd->td",
        _swiglu(jnp.einsum("td,df->tf", x, blk["shared_gate_up"])),
        blk["shared_down"],
    )


def sparse_mlp(h, blk, config: LagunaConfig):
    flat = h.reshape(-1, h.shape[-1])
    return (routed_experts(flat, blk, config) + shared_expert(flat, blk)).reshape(
        h.shape
    )


def layer(x, blk, kind: str, mlp: str, config: LagunaConfig):
    eps = config.rms_norm_eps
    x = x + attention(rms_norm(x, blk["attn_norm"], eps), blk, kind, config)
    h = rms_norm(x, blk["mlp_norm"], eps)
    return x + (dense_mlp(h, blk) if mlp == DENSE else sparse_mlp(h, blk, config))


# -------------------------------------------------------- model and step


def forward(params, tokens, config: LagunaConfig):
    """Logits [B, T, V] in float32 over the vocabulary rows held here."""
    x = params["embed"][tokens]
    for kind, mlp, blk in zip(
        config.layer_types, config.mlp_layer_types, params["layers"]
    ):
        run = lambda x, blk, kind=kind, mlp=mlp: layer(x, blk, kind, mlp, config)
        x = (jax.checkpoint(run) if config.remat else run)(x, blk)
    x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    return jnp.einsum("btd,dv->btv", x, params["head"], preferred_element_type=_F32)


def loss_fn(params, tokens, config: LagunaConfig):
    """Next-token cross entropy over the held rows of the vocabulary."""
    logp = jax.nn.log_softmax(forward(params, tokens, config)[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def adamw_train_step(state, tokens, config: LagunaConfig, hp: AdamW = AdamW()):
    """One step; gradients in the compute dtype. Returns (state, loss)."""
    loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens, config)
    return adamw_update(state, grads, hp), loss
