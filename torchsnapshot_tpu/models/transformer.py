"""Flagship workload: a decoder-only transformer with dp/sp/tp shardings.

The reference has no model code — its flagship workload is a torchrec DLRM
whose row-wise-sharded embedding tables drive the sharded-checkpoint path
(reference examples/torchrec_example.py:85-128). The TPU build's flagship
is a pjit transformer: it exercises every state category the snapshot
layer supports (tp-sharded matrices, dp-replicated scales, optimizer
moments mirroring the params, PRNG keys, host-side progress), and it is
the model the driver compile-checks (`__graft_entry__.py`) and the
benchmark trains.

TPU-first design notes:
- all matmuls are einsums over [B, S, D] x [D, ...] — large, batched,
  MXU-shaped; params bf16-able (kept f32 here for optimizer exactness,
  cast at use via `cast_dtype`);
- sharding: weights tp-sharded on their hidden dims, activations
  constrained to P(dp, sp, None) so sequence parallelism rides the mesh's
  "sp" axis; XLA inserts the all-gathers/reduce-scatters over ICI;
- static shapes, no data-dependent control flow: the whole train step is
  one jit program.
"""

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import flash_attention, resolve_flash_block
from ..parallel.mesh import shard_pytree


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    # Key/value heads (grouped-query attention): n_heads % n_kv_heads
    # == 0; q-head h attends kv-head h // group. None = n_heads (dense MHA).
    n_kv_heads: Any = None
    n_layers: int = 2
    d_ff: int = 512
    max_seq_len: int = 128
    dtype: Any = jnp.float32
    # Use the fused Pallas attention kernel (ops/attention.py) instead of
    # materializing the S×S score matrix. Off by default: the einsum path
    # is the numerical reference (the kernel's online softmax reassociates
    # reductions, so outputs match to float tolerance, not bitwise).
    flash_attention: bool = False


def _n_kv_heads(config: "TransformerConfig") -> int:
    """Normalized kv-head count: None = dense MHA; 0 or a non-divisor of
    n_heads is a configuration error, not a silent fallback."""
    n_kv = config.n_kv_heads
    if n_kv is None:
        return config.n_heads
    if n_kv <= 0 or config.n_heads % n_kv:
        raise ValueError(
            f"n_heads ({config.n_heads}) must be a positive multiple of "
            f"n_kv_heads ({n_kv})"
        )
    return n_kv


def init_params(config: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """Plain-container pytree of parameters (snapshot-friendly)."""
    keys = jax.random.split(key, config.n_layers + 2)
    scale = 1.0 / np.sqrt(config.d_model)

    def dense(k, shape):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(
            config.dtype
        )

    n_kv = _n_kv_heads(config)
    kv_dim = (config.d_model // config.n_heads) * n_kv
    layers = []
    for i in range(config.n_layers):
        lk = jax.random.split(keys[i], 6)
        layers.append(
            {
                "attn": {
                    "wq": dense(lk[0], (config.d_model, config.d_model)),
                    "wk": dense(lk[1], (config.d_model, kv_dim)),
                    "wv": dense(lk[2], (config.d_model, kv_dim)),
                    "wo": dense(lk[3], (config.d_model, config.d_model)),
                },
                "mlp": {
                    "w1": dense(lk[4], (config.d_model, config.d_ff)),
                    "w2": dense(lk[5], (config.d_ff, config.d_model)),
                },
                "ln1": jnp.ones((config.d_model,), dtype=jnp.float32),
                "ln2": jnp.ones((config.d_model,), dtype=jnp.float32),
            }
        )
    return {
        "embed": dense(keys[-2], (config.vocab_size, config.d_model)),
        "pos_embed": dense(keys[-1], (config.max_seq_len, config.d_model)),
        "final_ln": jnp.ones((config.d_model,), dtype=jnp.float32),
        "layers": layers,
    }


def param_sharding_rules(keys: Tuple[str, ...], leaf: Any) -> Optional[P]:
    """tp-shard the big matrices; replicate norms and positions.

    Column-parallel (wq/wk/wv/w1) shard the output dim; row-parallel
    (wo/w2) shard the input dim — the Megatron layout, expressed as
    shardings for XLA to lower onto ICI collectives.
    """
    name = keys[-1]
    if name in ("wq", "wk", "wv", "w1"):
        return P(None, "tp")
    if name in ("wo", "w2"):
        return P("tp", None)
    if name == "embed":
        return P("tp", None)  # vocab-sharded
    return P()


def _layer_norm(x, scale):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-6) * scale


def _activation_spec(mesh: Optional[Mesh]) -> Optional[P]:
    if mesh is None:
        return None
    names = mesh.axis_names
    return P(
        "dp" if "dp" in names else None,
        "sp" if "sp" in names else None,
        None,
    )


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,  # [B, S] int32
    config: TransformerConfig,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Logits [B, S, V]. Pure function; jit/pjit-able."""
    act_spec = _activation_spec(mesh)

    def constrain(x):
        if act_spec is not None:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, act_spec)
            )
        return x

    _, seq_len = tokens.shape
    h = params["embed"][tokens] + params["pos_embed"][:seq_len]
    h = constrain(h.astype(config.dtype))

    if config.flash_attention and mesh is not None:
        # pallas_call has no SPMD partitioning rule: under a mesh with
        # sp-sharded activations it would fail to lower (or silently
        # replicate), defeating the sequence parallelism this model
        # advertises. Sharded attention needs a ring/all-to-all kernel —
        # use the einsum path on meshes until then.
        raise ValueError(
            "flash_attention currently supports single-device (per-host) "
            "execution only; drop the mesh or use the einsum path."
        )
    mask = (
        None
        if config.flash_attention
        else jnp.tril(jnp.ones((seq_len, seq_len), dtype=bool))
    )
    head_dim = config.d_model // config.n_heads
    n_kv_heads = _n_kv_heads(config)

    for layer in params["layers"]:
        x = _layer_norm(h, layer["ln1"])
        q = jnp.einsum("bsd,dh->bsh", x, layer["attn"]["wq"])
        k = jnp.einsum("bsd,dh->bsh", x, layer["attn"]["wk"])
        v = jnp.einsum("bsd,dh->bsh", x, layer["attn"]["wv"])
        q = q.reshape(*q.shape[:2], config.n_heads, head_dim)
        k = k.reshape(*k.shape[:2], n_kv_heads, head_dim)
        v = v.reshape(*v.shape[:2], n_kv_heads, head_dim)
        if config.flash_attention:
            block = resolve_flash_block(seq_len)
            attn = flash_attention(
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                causal=True,
                block_q=block,
                block_k=block,
            ).transpose(0, 2, 1, 3)
        else:
            if n_kv_heads != config.n_heads:
                # Dense einsum is the numerical reference path; repeating
                # kv heads is the textbook GQA semantics (the kernels
                # avoid the materialization; this path keeps it simple).
                group = config.n_heads // n_kv_heads
                k = jnp.repeat(k, group, axis=2)
                v = jnp.repeat(v, group, axis=2)
            scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / np.sqrt(head_dim)
            scores = jnp.where(mask[None, None, :, :], scores, -1e30)
            probs = jax.nn.softmax(
                scores.astype(jnp.float32), axis=-1
            ).astype(config.dtype)
            attn = jnp.einsum("bnqk,bknd->bqnd", probs, v)
        attn = attn.reshape(*attn.shape[:2], config.d_model)
        h = h + constrain(jnp.einsum("bsh,hd->bsd", attn, layer["attn"]["wo"]))

        x = _layer_norm(h, layer["ln2"])
        ff = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, layer["mlp"]["w1"]))
        h = h + constrain(jnp.einsum("bsf,fd->bsd", ff, layer["mlp"]["w2"]))

    h = _layer_norm(h, params["final_ln"])
    logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])  # tied head
    return logits.astype(jnp.float32)


def loss_fn(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: TransformerConfig,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Next-token cross entropy."""
    logits = forward(params, tokens, config, mesh)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll)


def sgd_train_step(
    params: Dict[str, Any],
    tokens: jax.Array,
    config: TransformerConfig,
    mesh: Optional[Mesh] = None,
    lr: float = 1e-2,
) -> Tuple[Dict[str, Any], jax.Array]:
    """One SGD step — self-contained (no optax) so __graft_entry__ can jit
    the *full* training step without external state plumbing."""
    loss, grads = jax.value_and_grad(partial(loss_fn, config=config, mesh=mesh))(
        params, tokens
    )
    new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype), params, grads)
    return new_params, loss


def shard_params(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    return shard_pytree(params, mesh, param_sharding_rules)
