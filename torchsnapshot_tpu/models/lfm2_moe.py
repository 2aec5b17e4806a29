"""LFM2-MoE as a load generator: the stack of ``LiquidAI/LFM2-8B-A1B``
(``model_type`` ``lfm2_moe``) under mixed-precision AdamW, for one
chip's share of an expert-parallel deployment.

Like ``nemotron_h.py``, ``laguna.py`` and ``sdar.py`` this is the load
whose state is checkpointed, not the product. A layer is pre-norm
residual, ``h = x + op(RMSNorm(x))`` and ``y = h + ffn(RMSNorm(h))``, no
bias anywhere, and layers differ by the operator ``layer_types[l]``
names:

- ``conv``, a gated short convolution. ``[B, C, X] = split3(x W_in)``
  (``W_in`` d x 3 d, in that order), ``u = B * X``, a depthwise causal
  convolution over time of ``conv_L_cache`` taps without bias,
  ``v_t = sum_j k_j * u_{t-(L-1)+j}`` (``nemotron_h.causal_conv1d``), and
  ``(C * v) W_out``.
- ``full_attention``. ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``hidden_size /
  num_attention_heads``; queries and keys each normed per head by an
  RMSNorm with a learned weight, then rotary on every dimension in the
  ``rotate_half`` form at ``rope_theta``; causal, through
  ``ops/attention.py``.

and by the feed-forward: the first ``num_dense_layers`` are a SwiGLU of
``intermediate_size``; every later one is sparse: ``s = sigmoid(h Wr)``
over all ``num_experts`` of the router, the top k of ``s + b`` (the
expert bias ``b`` enters the choice alone), the chosen ``s`` divided by
their sum and times ``routed_scaling_factor``, applied to SwiGLU
experts of which this chip holds ``expert_ids`` and adds its own
experts' part alone (``experts.py``, shared with the other three
models, as are the optimizer, ``mixed_adamw.py``, and the RMSNorm). No
shared expert. No token is dropped.

Gate and up projections are one fused leaf, ``[.., d, 2 f]``, gate
first. The embedding over the vocabulary rows held here is also the
head (tied), after a final RMSNorm; next-token cross entropy over the
held rows.

**The expert bias** is no parameter: no gradient moves it. It is held
among the compute copies, ``params["expert_bias"]``, one float32
``[num_experts]`` a sparse layer, as a ``state_dict`` holds a buffer
with the weights; neither the master nor the moments hold it. A step
moves it after the AdamW update by the auxiliary-loss-free rule
(DeepSeek-V3, arXiv:2412.19437 §2.1.2): ``b_i <- b_i + rate *
sign(mean load - load_i)``, the loads counted over all the router's
experts from this chip's own tokens in that step's forward pass. 16
bytes a parameter resident and 14 saved, as for every ``mixed_adamw``
model, plus the biases.
"""

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import flash_attention, resolve_flash_block
from . import experts
from .mixed_adamw import AdamW, adamw_update, state_of_master
from .nemotron_h import causal_conv1d, rms_norm

_F32 = jnp.float32
_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
CONV, ATTENTION = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """Published sizes by default; ``layer_types`` (the layers held),
    ``expert_ids`` and ``vocab_size`` are what one chip of the
    deployment holds."""

    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTENTION, CONV, CONV, CONV)
    num_dense_layers: int = 2
    vocab_size: int = 16384
    norm_eps: float = 1e-5
    # the short convolution
    conv_L_cache: int = 3
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0
    flash_attention: bool = True
    # feed-forward
    intermediate_size: int = 7168
    num_experts: int = 32  # the router's width
    expert_ids: Tuple[int, ...] = tuple(range(8))
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    expert_capacity: int = 0  # see experts.Routing.capacity
    expert_dense_group: int = 0  # see experts.Routing.dense_group
    expert_bias_rate: float = 0.001
    # numerics
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def sparse_layers(self) -> List[int]:
        return list(range(self.num_dense_layers, len(self.layer_types)))

    @property
    def routing(self) -> experts.Routing:
        return experts.Routing(
            expert_ids=self.expert_ids,
            top_k=self.num_experts_per_tok,
            normalise=self.norm_topk_prob,
            scaling_factor=self.routed_scaling_factor,
            capacity=self.expert_capacity,
            dense_group=self.expert_dense_group,
        )


def config_from_published(config: Dict[str, Any]) -> Lfm2MoeConfig:
    """The program's configuration from a configuration file's keys:
    the published ones of ``config.json`` (``num_experts`` the experts
    held, their ids under ``expert_ids``, the router's width under
    ``published``; ``vocab_size`` the rows held; ``layers_held`` of the
    published ``layer_types``) and the job's ``attention``,
    ``expert_capacity``, ``expert_dense_group``, ``expert_bias_rate``,
    ``param_dtype``, ``remat``. What the model cannot run is refused
    aloud."""
    if config["conv_bias"]:
        raise ValueError("models/lfm2_moe.py has a short convolution without bias")
    if not config["use_expert_bias"]:
        raise ValueError("models/lfm2_moe.py routes with the expert bias")
    if not config.get("tie_word_embeddings", True):
        raise ValueError("models/lfm2_moe.py ties the head to the embedding")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("the head width is hidden_size / num_attention_heads")
    if len(config["expert_ids"]) != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: one id each")
    layer_types = tuple(config["layer_types"][: config["layers_held"]])
    if set(layer_types) - {CONV, ATTENTION}:
        raise ValueError(f"models/lfm2_moe.py runs {CONV} and {ATTENTION} layers")
    return Lfm2MoeConfig(
        hidden_size=config["hidden_size"],
        layer_types=layer_types,
        num_dense_layers=config["num_dense_layers"],
        vocab_size=config["vocab_size"],
        norm_eps=config["norm_eps"],
        conv_L_cache=config["conv_L_cache"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        rope_theta=float(config["rope_theta"]),
        flash_attention=config["attention"] == "flash",
        intermediate_size=config["intermediate_size"],
        num_experts=config["published"]["num_experts"],
        expert_ids=tuple(config["expert_ids"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        expert_capacity=config.get("expert_capacity", 0),
        expert_dense_group=config.get("expert_dense_group", 0),
        expert_bias_rate=config["expert_bias_rate"],
        dtype=_DTYPES[config["param_dtype"]],
        remat=config.get("remat", True),
    )


# ------------------------------------------------------------------ init


def init_master(config: Lfm2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    """Float32 parameters from a key: normal(0, 0.02) matrices and
    convolution taps, norms 1."""
    d, hd = config.hidden_size, config.head_dim
    keys = iter(jax.random.split(key, 8 * len(config.layer_types) + 1))

    def dense(*shape):
        return jax.random.normal(next(keys), shape, _F32) * 0.02

    def layer(index, kind):
        blk = {"op_norm": jnp.ones((d,), _F32), "ffn_norm": jnp.ones((d,), _F32)}
        if kind == CONV:
            blk.update(
                in_proj=dense(d, 3 * d),
                conv_w=dense(config.conv_L_cache, d),
                out_proj=dense(d, d),
            )
        else:
            kv = config.num_key_value_heads * hd
            blk.update(
                wq=dense(d, config.num_attention_heads * hd),
                wk=dense(d, kv),
                wv=dense(d, kv),
                q_norm=jnp.ones((hd,), _F32),
                k_norm=jnp.ones((hd,), _F32),
                wo=dense(config.num_attention_heads * hd, d),
            )
        if index < config.num_dense_layers:
            f = config.intermediate_size
            blk.update(gate_up=dense(d, 2 * f), down=dense(f, d))
        else:
            held, f = len(config.expert_ids), config.moe_intermediate_size
            blk.update(
                router=dense(d, config.num_experts),
                gate_up=dense(held, d, 2 * f),
                down=dense(held, f, d),
            )
        return blk

    return {
        "embed": dense(config.vocab_size, d),
        "layers": [layer(i, kind) for i, kind in enumerate(config.layer_types)],
        "final_norm": jnp.ones((d,), _F32),
    }


def init_state(config: Lfm2MoeConfig, key: jax.Array) -> Dict[str, Any]:
    """The whole training state, jit-able (``mixed_adamw``), with the
    expert biases, zero, among the params."""
    state = state_of_master(init_master(config, key), config.dtype)
    state["params"]["expert_bias"] = [
        jnp.zeros((config.num_experts,), _F32) for _ in config.sparse_layers
    ]
    return state


def split_biases(params) -> Tuple[Dict[str, Any], List[jax.Array]]:
    """The weights a gradient moves, and the expert biases."""
    weights = {name: part for name, part in params.items() if name != "expert_bias"}
    return weights, params["expert_bias"]


def draw_tokens(key, shape, config: Lfm2MoeConfig):
    """Token ids from the vocabulary rows held."""
    return jax.random.randint(key, shape, 0, config.vocab_size)


# ---------------------------------------------------------------- blocks


def apply_rope(x, theta: float):
    """Rotary on every dimension of each head of ``x`` [B, T, H,
    head_dim], positions 0..T-1, ``rotate_half`` form, in float32.
    Result in ``x``'s dtype."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=_F32)[:, None] * jnp.asarray(inv_freq)
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


def short_conv(h, blk):
    """The gated short convolution on its normed input ``h`` [B, T, d]."""
    b, c, x = jnp.split(jnp.einsum("btd,de->bte", h, blk["in_proj"]), 3, axis=-1)
    v = causal_conv1d(b * x, blk["conv_w"])
    return jnp.einsum("btd,de->bte", c * v, blk["out_proj"])


def attention(h, blk, config: Lfm2MoeConfig):
    """Causal grouped-query attention on its normed input ``h`` [B, T, d]."""
    batch, t, _ = h.shape
    heads, kv, hd = config.num_attention_heads, config.num_key_value_heads, config.head_dim
    eps = config.norm_eps
    q = jnp.einsum("btd,dh->bth", h, blk["wq"]).reshape(batch, t, heads, hd)
    k = jnp.einsum("btd,dh->bth", h, blk["wk"]).reshape(batch, t, kv, hd)
    v = jnp.einsum("btd,dh->bth", h, blk["wv"]).reshape(batch, t, kv, hd)
    q = apply_rope(rms_norm(q, blk["q_norm"], eps), config.rope_theta)
    k = apply_rope(rms_norm(k, blk["k_norm"], eps), config.rope_theta)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    if config.flash_attention:
        tile = resolve_flash_block(t)
        out = flash_attention(q, k, v, causal=True, block_q=tile, block_k=tile)
    else:
        k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=_F32
        ) / np.sqrt(hd)
        seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        scores = jnp.where(seen, scores, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores).astype(h.dtype), v)
    out = out.transpose(0, 2, 1, 3).reshape(batch, t, heads * hd)
    return jnp.einsum("bth,hd->btd", out, blk["wo"])


def dense_mlp(h, blk):
    return jnp.einsum(
        "btf,fd->btd", _swiglu(jnp.einsum("btd,df->btf", h, blk["gate_up"])), blk["down"]
    )


def sparse_mlp(h, blk, bias, config: Lfm2MoeConfig):
    """The held experts' part of the layer's result on ``h`` [B, T, d],
    and the tokens each of the router's experts was chosen for."""
    flat = h.reshape(-1, h.shape[-1])
    out = experts.routed_experts(
        flat, blk["router"], bias, lambda project: _swiglu(project(blk["gate_up"])),
        blk["down"], config.routing,
    )
    return out.reshape(h.shape), experts.expert_loads(
        flat, blk["router"], bias, config.routing
    )


def layer(x, blk, bias, kind: str, config: Lfm2MoeConfig):
    """One layer; ``bias`` is None for a dense one. Returns the layer's
    output and, for a sparse one, its experts' loads."""
    eps = config.norm_eps
    op = short_conv if kind == CONV else lambda h, blk: attention(h, blk, config)
    x = x + op(rms_norm(x, blk["op_norm"], eps), blk)
    h = rms_norm(x, blk["ffn_norm"], eps)
    if bias is None:
        return x + dense_mlp(h, blk), None
    out, loads = sparse_mlp(h, blk, bias, config)
    return x + out, loads


# -------------------------------------------------------- model and step


def hidden(params, expert_bias, tokens, config: Lfm2MoeConfig):
    """The final-normed residual stream [B, T, d] and, a sparse layer,
    the loads of the router's experts (``int32 [num_experts]``)."""
    x = params["embed"][tokens]
    biases = iter(expert_bias)
    loads = []
    for index, (kind, blk) in enumerate(zip(config.layer_types, params["layers"])):
        bias = next(biases) if index >= config.num_dense_layers else None
        run = lambda x, blk, bias, kind=kind: layer(x, blk, bias, kind, config)
        x, load = (jax.checkpoint(run) if config.remat else run)(x, blk, bias)
        if load is not None:
            loads.append(load)
    return rms_norm(x, params["final_norm"], config.norm_eps), loads


def forward(params, expert_bias, tokens, config: Lfm2MoeConfig):
    """Logits [B, T, V] in float32 over the vocabulary rows held here
    (the head is the embedding)."""
    x, _ = hidden(params, expert_bias, tokens, config)
    return jnp.einsum("btd,vd->btv", x, params["embed"], preferred_element_type=_F32)


def loss_fn(params, expert_bias, tokens, config: Lfm2MoeConfig):
    """Next-token cross entropy over the held rows of the vocabulary, and
    the loads of every sparse layer's experts."""
    x, loads = hidden(params, expert_bias, tokens, config)
    logits = jnp.einsum(
        "btd,vd->btv", x[:, :-1], params["embed"], preferred_element_type=_F32
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)), loads


def update_expert_bias(expert_bias, loads, rate: float):
    """``b_i + rate * sign(mean load - load_i)``, a sparse layer."""
    return [
        b + rate * jnp.sign(jnp.mean(n.astype(_F32)) - n.astype(_F32))
        for b, n in zip(expert_bias, loads)
    ]


def adamw_train_step(state, tokens, key, config: Lfm2MoeConfig, hp: AdamW = AdamW()):
    """One step; gradients in the compute dtype, then the expert biases
    moved by the step's loads. ``key`` is unused: the objective draws no
    noise. Returns (state, loss)."""
    del key
    weights, biases = split_biases(state["params"])
    (loss, loads), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        weights, biases, tokens, config
    )
    state = adamw_update({**state, "params": weights}, grads, hp)
    state["params"]["expert_bias"] = update_expert_bias(
        biases, loads, config.expert_bias_rate
    )
    return state, loss
