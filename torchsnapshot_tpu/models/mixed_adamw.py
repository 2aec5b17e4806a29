"""Mixed-precision AdamW as a pre-training rank holds it: compute copies
of the parameters in the model's dtype, a float32 master, two float32
moments and an int32 count. What ``nemotron_h.py`` and ``laguna.py``
both step with.

The training state is ``{"params": compute copies, "master": float32,
"opt": (Moments(mu, nu) in float32, int32 count)}``: 16 bytes a
parameter resident with a step's gradients (in the compute dtype), 14
saved.
"""

import dataclasses
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

_F32 = jnp.float32


class Moments(NamedTuple):
    mu: Any
    nu: Any


@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float = 1e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1  # decoupled, on leaves of two or more axes


def state_of_master(master, dtype) -> Dict[str, Any]:
    """The whole training state around float32 parameters, jit-able:
    their compute copies, zeroed moments, count 0."""
    zeros = jax.tree.map(jnp.zeros_like, master)
    return {
        "params": jax.tree.map(lambda m: m.astype(dtype), master),
        "master": master,
        "opt": (Moments(zeros, zeros), jnp.zeros((), jnp.int32)),
    }


def adamw_update(state, grads, hp: AdamW):
    """Mixed-precision AdamW: moments and master in float32, the compute
    copies recast from the master; decoupled weight decay on leaves of
    two or more axes."""
    (moments, count), master = state["opt"], state["master"]
    count = count + 1
    t = count.astype(_F32)
    grads = jax.tree.map(lambda g: g.astype(_F32), grads)
    mu = jax.tree.map(lambda m, g: hp.b1 * m + (1 - hp.b1) * g, moments.mu, grads)
    nu = jax.tree.map(lambda n, g: hp.b2 * n + (1 - hp.b2) * g * g, moments.nu, grads)

    def step(w, m, n):
        update = (m / (1 - hp.b1**t)) / (jnp.sqrt(n / (1 - hp.b2**t)) + hp.eps)
        if w.ndim >= 2:
            update = update + hp.weight_decay * w
        return w - hp.lr * update

    master = jax.tree.map(step, master, mu, nu)
    dtype_of = jax.tree.map(lambda p: p.dtype, state["params"])
    return {
        "params": jax.tree.map(lambda w, d: w.astype(d), master, dtype_of),
        "master": master,
        "opt": (Moments(mu, nu), count),
    }
