"""Nemotron-H as a load generator: the hybrid stack of
``NVIDIA-Nemotron-3-Nano-30B-A3B`` (``model_type`` ``nemotron_h``) under
mixed-precision AdamW, for one chip's share of an expert-parallel
deployment.

Like ``transformer.py`` this is the load whose state is checkpointed,
not the product. Blocks are pre-norm residual, ``x + f(RMSNorm(x))``,
one ``f`` a block, in the order ``NemotronHConfig.pattern`` gives:

- ``M``: Mamba-2. ``[z, xBC, dt] = x W_in``; ``xBC = silu(conv1d(xBC))``
  (causal, depthwise, kernel 4, bias); split into ``x`` (heads x head
  dim), ``B``, ``C`` (groups x state); ``dt = clip(softplus(dt +
  dt_bias), time_step_limit)``; ``A = -exp(A_log)`` a head;
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t h_t + D
  x_t``, computed in chunks (:func:`ssd_chunked`, plain ``jax.numpy``);
  ``y = RMSNorm_grouped(y * silu(z))``; out ``y W_out``.
- ``E``: mixture of experts. ``s = sigmoid(x W_r)``; the top k of all
  ``n_routed_experts`` by ``s + correction_bias``; weights = the chosen
  ``s``, normalised to sum 1, times ``routed_scaling_factor``; expert
  ``e(x) = W_down relu(W_up x)^2`` (no gate); plus one shared expert on
  every token. The layer is told which experts it holds
  (``expert_ids``), routes over all of them at the published router
  width, and adds only its own experts' part: what the absent experts
  would have added is left out, as expert parallelism leaves it to the
  other chips. No token is dropped.
- ``*``: attention. Grouped-query, causal, no bias, no positional
  embedding (Nemotron-H applies none), through ``ops/attention.py``.

Untied embedding and head over the vocabulary rows held here, a final
RMSNorm, next-token cross entropy over the held rows.

The training state is ``{"params": compute copies in ``dtype``,
"master": float32, "opt": (Moments(mu, nu) in float32, int32 count)}``:
16 bytes a parameter resident with the step's gradients, 14 saved.

Where this departs from the source's code (``modeling_nemotron_h.py``):
the compute copies of ``A_log``, ``D``, ``dt_bias`` and the router's
correction bias are held in ``dtype`` like every other parameter (the
source keeps them float32; here the float32 master holds them), and are
cast to float32 where they are used.
"""

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import flash_attention, resolve_flash_block
from . import experts
from .mixed_adamw import AdamW, Moments, adamw_update, state_of_master  # noqa: F401

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Published sizes by default; ``pattern``, ``expert_ids`` and
    ``vocab_size`` are what one chip of the deployment holds."""

    hidden_size: int = 2688
    pattern: str = "MEMEM*EME"
    vocab_size: int = 16384
    num_hidden_layers: int = 52  # published depth: scales the out projections' init
    norm_eps: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    time_step_limit: Tuple[float, float] = (0.0, math.inf)
    # experts
    n_routed_experts: int = 128
    expert_ids: Tuple[int, ...] = tuple(range(8))
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # Rows a held expert computes a step: 0 = every token (dense over the
    # held experts). Otherwise tokens are gathered into that many slots an
    # expert, and a step in which some expert is sent more falls back to
    # the dense computation, so that no token is ever dropped.
    expert_capacity: int = 0
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    flash_attention: bool = True
    # numerics
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def routing(self) -> experts.Routing:
        return experts.Routing(
            expert_ids=self.expert_ids,
            top_k=self.num_experts_per_tok,
            normalise=self.norm_topk_prob,
            scaling_factor=self.routed_scaling_factor,
            capacity=self.expert_capacity,
        )


# ------------------------------------------------------------------ init


def init_master(config: NemotronHConfig, key: jax.Array) -> Dict[str, Any]:
    """Float32 parameters from a key: normal(0, 0.02) matrices, the out
    projections scaled by 1/sqrt(published depth) (``rescale_prenorm_
    residual``), ``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the
    inverse softplus of a log-uniform time step, norms 1, biases 0."""
    d = config.hidden_size
    keys = iter(jax.random.split(key, 8 * len(config.pattern) + 2))
    out_scale = 1.0 / math.sqrt(config.num_hidden_layers)

    def dense(shape, scale=1.0):
        return jax.random.normal(next(keys), shape, _F32) * (0.02 * scale)

    def mamba():
        heads, inner = config.mamba_num_heads, config.mamba_inner
        dt = jnp.exp(
            jax.random.uniform(next(keys), (heads,), _F32)
            * (math.log(config.time_step_max) - math.log(config.time_step_min))
            + math.log(config.time_step_min)
        )
        dt = jnp.maximum(dt, config.time_step_floor)
        return {
            "norm": jnp.ones((d,), _F32),
            "in_proj": dense((d, inner + config.conv_dim + heads)),
            "conv_w": dense((config.conv_kernel, config.conv_dim), 10.0),
            "conv_b": jnp.zeros((config.conv_dim,), _F32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=_F32)),
            "D": jnp.ones((heads,), _F32),
            "gate_norm": jnp.ones((inner,), _F32),
            "out_proj": dense((inner, d), out_scale),
        }

    def experts():
        held = len(config.expert_ids)
        f, fs = config.moe_intermediate_size, config.moe_shared_expert_intermediate_size
        return {
            "norm": jnp.ones((d,), _F32),
            "router": dense((d, config.n_routed_experts)),
            "router_bias": jnp.zeros((config.n_routed_experts,), _F32),
            "up": dense((held, d, f)),
            "down": dense((held, f, d), out_scale),
            "shared_up": dense((d, fs)),
            "shared_down": dense((fs, d), out_scale),
        }

    def attention():
        hq = config.num_attention_heads * config.head_dim
        hkv = config.num_key_value_heads * config.head_dim
        return {
            "norm": jnp.ones((d,), _F32),
            "wq": dense((d, hq)),
            "wk": dense((d, hkv)),
            "wv": dense((d, hkv)),
            "wo": dense((hq, d), out_scale),
        }

    make = {"M": mamba, "E": experts, "*": attention}
    return {
        "embed": dense((config.vocab_size, d)),
        "blocks": [make[kind]() for kind in config.pattern],
        "final_norm": jnp.ones((d,), _F32),
        "head": dense((d, config.vocab_size)),
    }


def init_state(config: NemotronHConfig, key: jax.Array) -> Dict[str, Any]:
    """The whole training state, jit-able: master weights, their compute
    copies, zeroed moments, count 0."""
    return state_of_master(init_master(config, key), config.dtype)


# ---------------------------------------------------------------- blocks


def rms_norm(x, weight, eps, group_size=None):
    """RMSNorm in float32 over the last axis, or over groups of
    ``group_size`` of it; result in ``x``'s dtype."""
    x32 = x.astype(_F32)
    if group_size is not None:
        x32 = x32.reshape(*x32.shape[:-1], -1, group_size)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32.reshape(x.shape) * weight.astype(_F32)).astype(x.dtype)


def _mm(spec, *operands):
    """An einsum that accumulates and returns float32."""
    return jnp.einsum(spec, *operands, preferred_element_type=_F32)


def ssd_chunked(x, dt, a, b, c, chunk):
    """The Mamba-2 recurrence ``h_t = exp(dt_t a) h_{t-1} + dt_t b_t
    x_t^T``, ``y_t = c_t h_t`` from ``h = 0``, computed chunk by chunk
    (state-space duality): inside a chunk as a masked, decayed attention,
    across chunks as a scan over the chunks' end states.

    ``x`` [B, T, H, P]; ``dt`` [B, T, H] float32 (after softplus);
    ``a`` [H] float32, negative; ``b``, ``c`` [B, T, G, N] with H a
    multiple of G. Returns [B, T, H, P] float32. ``T`` need not be a
    multiple of ``chunk``: the tail is padded with ``dt = 0``, which
    neither decays nor feeds the state.
    """
    batch, t, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    q = heads // groups
    pad = -t % chunk
    if pad:
        widen = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    chunks = (t + pad) // chunk
    dtype = x.dtype
    # [B, C, L, G, Q, ...]: heads as (group, head in group), so B and C
    # are never copied per head.
    dt = dt.reshape(batch, chunks, chunk, groups, q)
    x_dt = (x.astype(_F32).reshape(batch, chunks, chunk, groups, q, p)
            * dt[..., None]).astype(dtype)
    b = b.reshape(batch, chunks, chunk, groups, n)
    c = c.reshape(batch, chunks, chunk, groups, n)
    cum = jnp.cumsum(dt * a.reshape(groups, q), axis=2)  # log decay since chunk start

    # Inside a chunk: y_l = sum_{s<=l} (c_l . b_s) exp(cum_l - cum_s) dt_s x_s.
    visible = jnp.tril(jnp.ones((chunk, chunk), bool))
    span = cum[:, :, :, None] - cum[:, :, None, :]  # [B, C, L, S, G, Q]
    decay = jnp.exp(jnp.where(visible[:, :, None, None], span, -jnp.inf))
    scores = _mm("zclgn,zcsgn->zclsg", c, b)[..., None] * decay
    y = _mm("zclsgq,zcsgqp->zclgqp", scores.astype(dtype), x_dt)

    # Each chunk's contribution to the state at its end, then the state
    # entering every chunk by a scan over chunks.
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, C, L, G, Q]
    x_end = (x_dt.astype(_F32) * to_end[..., None]).astype(dtype)
    local = _mm("zclgn,zclgqp->zcgqpn", b, x_end)
    chunk_decay = jnp.exp(cum[:, :, -1])  # [B, C, G, Q]

    def carry_over(state, inputs):
        decay_c, local_c = inputs
        return state * decay_c[..., None, None] + local_c, state

    _, entering = jax.lax.scan(
        carry_over,
        jnp.zeros((batch, groups, q, p, n), _F32),
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(local, 1, 0)),
    )
    entering = jnp.moveaxis(entering, 0, 1)  # [B, C, G, Q, P, N]
    y = y + _mm("zclgn,zcgqpn->zclgqp", c, entering.astype(dtype)) * jnp.exp(cum)[..., None]
    return y.reshape(batch, chunks * chunk, heads, p)[:, :t]


def causal_conv1d(x, weight, bias=None):
    """Depthwise causal convolution over time: ``out_t = sum_j w_j
    x_{t-(K-1)+j} + bias`` (no bias where None). ``x`` [B, T, C],
    ``weight`` [K, C]."""
    k = weight.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    out = sum(padded[:, j : j + t] * weight[j] for j in range(k))
    return out if bias is None else out + bias


def mamba_mixer(x, blk, config: NemotronHConfig):
    heads, p = config.mamba_num_heads, config.mamba_head_dim
    inner, gn = config.mamba_inner, config.n_groups * config.ssm_state_size
    proj = jnp.einsum("btd,de->bte", x, blk["in_proj"])
    z, xbc, dt = jnp.split(proj, [inner, inner + config.conv_dim], axis=-1)
    xbc = jax.nn.silu(causal_conv1d(xbc, blk["conv_w"], blk["conv_b"]))
    xs, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
    dt = jax.nn.softplus(dt.astype(_F32) + blk["dt_bias"].astype(_F32))
    dt = jnp.clip(dt, *config.time_step_limit)
    a = -jnp.exp(blk["A_log"].astype(_F32))
    shape = x.shape[:2]
    xs = xs.reshape(*shape, heads, p)
    y = ssd_chunked(
        xs,
        dt,
        a,
        b.reshape(*shape, config.n_groups, config.ssm_state_size),
        c.reshape(*shape, config.n_groups, config.ssm_state_size),
        config.chunk_size,
    )
    y = y + xs.astype(_F32) * blk["D"].astype(_F32)[:, None]
    y = y.reshape(*shape, inner) * jax.nn.silu(z.astype(_F32))
    y = rms_norm(
        y, blk["gate_norm"], config.norm_eps, group_size=inner // config.n_groups
    ).astype(x.dtype)
    return jnp.einsum("bte,ed->btd", y, blk["out_proj"])


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _relu2_of(up):
    """The body of this model's experts: ``relu(x W_up)^2``, no gate."""
    return lambda project: _relu2(project(up))


def held_gates(x, blk, config: NemotronHConfig):
    """``[tokens, held experts]`` float32 gates and which tokens go to
    which held expert (:func:`experts.held_gates`; the correction bias
    enters the choice alone)."""
    return experts.held_gates(x, blk["router"], blk["router_bias"], config.routing)


def _experts_dense(x, gates, up, down):
    return experts.experts_dense(x, gates, _relu2_of(up), down)


def _experts_gathered(x, gates, routed, up, down, capacity):
    return experts.experts_gathered(x, gates, routed, _relu2_of(up), down, capacity)


def routed_experts(x, blk, config: NemotronHConfig):
    """The held experts' part of the layer's result, ``x`` [tokens, d]."""
    return experts.routed_experts(
        x, blk["router"], blk["router_bias"], _relu2_of(blk["up"]), blk["down"],
        config.routing,
    )


def shared_expert(x, blk):
    return jnp.einsum(
        "tf,fd->td",
        _relu2(jnp.einsum("td,df->tf", x, blk["shared_up"])),
        blk["shared_down"],
    )


def moe_mixer(x, blk, config: NemotronHConfig):
    flat = x.reshape(-1, x.shape[-1])
    return (routed_experts(flat, blk, config) + shared_expert(flat, blk)).reshape(
        x.shape
    )


def attention_mixer(x, blk, config: NemotronHConfig):
    batch, t, _ = x.shape
    heads, kv, hd = (
        config.num_attention_heads,
        config.num_key_value_heads,
        config.head_dim,
    )
    q = jnp.einsum("btd,dh->bth", x, blk["wq"]).reshape(batch, t, heads, hd)
    k = jnp.einsum("btd,dh->bth", x, blk["wk"]).reshape(batch, t, kv, hd)
    v = jnp.einsum("btd,dh->bth", x, blk["wv"]).reshape(batch, t, kv, hd)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    if config.flash_attention:
        block = resolve_flash_block(t)
        out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    else:
        k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
        scores = _mm("bhqd,bhkd->bhqk", q, k) / np.sqrt(hd)
        scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores).astype(x.dtype), v)
    out = out.transpose(0, 2, 1, 3).reshape(batch, t, heads * hd)
    return jnp.einsum("bth,hd->btd", out, blk["wo"])


_MIXERS = {"M": mamba_mixer, "E": moe_mixer, "*": attention_mixer}


def block(x, blk, kind: str, config: NemotronHConfig):
    return x + _MIXERS[kind](rms_norm(x, blk["norm"], config.norm_eps), blk, config)


# -------------------------------------------------------- model and step


def forward(params, tokens, config: NemotronHConfig):
    """Logits [B, T, V] in float32 over the vocabulary rows held here."""
    x = params["embed"][tokens]
    for kind, blk in zip(config.pattern, params["blocks"]):
        run = lambda x, blk, kind=kind: block(x, blk, kind, config)
        x = (jax.checkpoint(run) if config.remat else run)(x, blk)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return _mm("btd,dv->btv", x, params["head"])


def loss_fn(params, tokens, config: NemotronHConfig):
    """Next-token cross entropy over the held rows of the vocabulary."""
    logp = jax.nn.log_softmax(forward(params, tokens, config)[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def adamw_train_step(state, tokens, config: NemotronHConfig, hp: AdamW = AdamW()):
    """One step; gradients in the compute dtype. Returns (state, loss)."""
    loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens, config)
    return adamw_update(state, grads, hp), loss
