"""The training job whose state is saved: the program's flagship
transformer (``models/transformer.py``) built from a configuration
file, with everything made on the device from ``--seed``.

The job is the load generator, not the product: the product is what
``CheckpointManager`` does with ``job.params``.
"""

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from torchsnapshot_tpu import PytreeStateful, StateDict
from torchsnapshot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    param_sharding_rules,
    sgd_train_step,
)
from torchsnapshot_tpu.parallel.mesh import make_mesh

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number: seeds pass 2**31, more than 32
    signed bits hold."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def key_names(path) -> tuple:
    """The plain names along a tree path, as the library spells them."""
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


class TrainJob:
    """Parameters, the jitted donating step and the token feed."""

    def __init__(self, config: Dict[str, Any], devices: List[Any], seed: int) -> None:
        if config.get("optimizer", "sgd") != "sgd":
            raise ValueError("models/transformer.py has SGD only")
        if config["d_model"] != config["n_heads"] * config["d_head"]:
            raise ValueError("d_model must be n_heads x d_head")
        self.config = config
        self.devices = devices
        self.seed = int(seed)
        self.mesh_axes: Optional[Dict[str, int]] = config.get("mesh")
        self.mesh = (
            make_mesh(self.mesh_axes, devices=devices) if self.mesh_axes else None
        )
        self.cfg = TransformerConfig(
            vocab_size=config["vocab_size"],
            d_model=config["d_model"],
            n_heads=config["n_heads"],
            n_layers=config["n_layers"],
            d_ff=config["d_ff"],
            max_seq_len=config["max_seq_len"],
            dtype=_DTYPES[config["param_dtype"]],
            flash_attention=config["attention"] == "flash",
        )
        self.batch = int(config["batch_size"])
        self.shapes = jax.eval_shape(
            lambda key: init_params(self.cfg, key), seed_key(0)
        )
        self.state_bytes = sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(self.shapes)
        )
        mesh, cfg = self.mesh, self.cfg
        self.step_fn = jax.jit(
            lambda p, t: sgd_train_step(p, t, cfg, mesh), donate_argnums=(0,)
        )
        self._tokens = jax.jit(
            lambda key, step: jax.random.randint(
                jax.random.fold_in(key, step),
                (self.batch, cfg.max_seq_len),
                0,
                cfg.vocab_size,
            ),
            out_shardings=NamedSharding(mesh, P("dp", None))
            if mesh is not None
            else jax.sharding.SingleDeviceSharding(devices[0]),
        )
        self._token_key = jax.random.fold_in(seed_key(self.seed), 1)
        self._zeros: Dict[Any, Any] = {}

    # ------------------------------------------------------------ layout

    def shardings(self, mesh_axes: Optional[Dict[str, int]] = None):
        """The sharding of every parameter on ``mesh_axes`` (default: the
        job's own layout): ``param_sharding_rules`` on a mesh, the first
        device without one."""
        if mesh_axes is None:
            mesh = self.mesh
        else:
            mesh = make_mesh(mesh_axes, devices=self.devices)
        if mesh is None:
            one = jax.sharding.SingleDeviceSharding(self.devices[0])
            return jax.tree.map(lambda _: one, self.shapes)
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.shapes)
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                NamedSharding(
                    mesh, param_sharding_rules(key_names(path), leaf) or P()
                )
                for path, leaf in flat
            ],
        )

    # ------------------------------------------------------------- state

    def init_params(self):
        """All parameters in one jitted call, made where they live."""
        cfg = self.cfg
        make = jax.jit(
            lambda key: init_params(cfg, key), out_shardings=self.shardings()
        )
        return make(jax.random.fold_in(seed_key(self.seed), 0))

    def zeros_template(self, mesh_axes: Optional[Dict[str, int]] = None):
        """A restore target that shares no bit with any saved state."""
        key = tuple(sorted(mesh_axes.items())) if mesh_axes else None
        if key not in self._zeros:
            shapes = self.shapes
            self._zeros[key] = jax.jit(
                lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), shapes
                ),
                out_shardings=self.shardings(mesh_axes),
            )
        return self._zeros[key]()

    def tokens(self, step: int) -> jax.Array:
        """The batch of step ``step``: a function of seed and step alone,
        ids below the vocabulary."""
        return self._tokens(self._token_key, np.uint32(step))

    @staticmethod
    def app_state(params, step: int) -> Dict[str, Any]:
        return {
            "train": PytreeStateful({"params": params}),
            "progress": StateDict(step=step),
        }

    def train_step(self, params, step: int):
        """One step, ended as real loops end it: fenced, loss fetched."""
        params, loss = self.step_fn(params, self.tokens(step))
        jax.block_until_ready(loss)
        return params, float(loss)
