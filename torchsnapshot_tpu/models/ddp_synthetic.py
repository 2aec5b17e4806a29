"""Synthetic DDP-style benchmark model.

TPU-native analog of reference benchmarks/ddp/main.py:38-39: a model that
is nothing but N large parameters (default 200 x ~100 MB = ~20 GB in the
reference; sized down per-config here): the state of the reference's
published benchmark, replicated and striped across ranks.
"""

from typing import Any, Dict, List

import jax
import jax.numpy as jnp


class SyntheticModel:
    """A Stateful of ``n_params`` dense arrays of ``param_bytes`` each."""

    def __init__(
        self,
        n_params: int = 200,
        param_bytes: int = 100 * 1024 * 1024,
        dtype: Any = jnp.float32,
        seed: int = 0,
    ) -> None:
        itemsize = jnp.dtype(dtype).itemsize
        n_elems = param_bytes // itemsize
        keys = jax.random.split(jax.random.key(seed), n_params)
        self.params: Dict[str, jax.Array] = {
            f"param_{i}": jax.random.normal(keys[i], (n_elems,), dtype=dtype)
            for i in range(n_params)
        }

    def state_dict(self) -> Dict[str, Any]:
        return dict(self.params)

    def load_state_dict(self, state_dict: Dict[str, Any]) -> None:
        self.params = dict(state_dict)

    def total_bytes(self) -> int:
        return sum(
            v.size * jnp.dtype(v.dtype).itemsize for v in self.params.values()
        )
