"""Job ``nemotron_h_adam``: one chip's share of a Nemotron-H stack
(``models/nemotron_h.py``: Mamba-2, mixture-of-experts and attention
blocks) under mixed-precision AdamW, built from a configuration file
with everything made on the device from ``--seed``.

The saved state is the whole training state,

    {"params": the compute copies (``param_dtype``),
     "master": float32,
     "opt":    (Moments(mu, nu) in float32, an int32 count)}

14 bytes a parameter saved, 16 resident with a step's gradients. It
goes to ``CheckpointManager`` as two ``PytreeStateful``s, ``model``
(params and master, plain containers) and ``optimizer`` (the tuple with
its named tuple, ``convert=True``), beside ``progress``.

The configuration's keys that are this job's own: the published keys of
the model's ``config.json`` (``hybrid_override_pattern`` whole, of which
the first ``layers_held`` blocks are held; ``n_routed_experts`` the
experts held, their ids under ``expert_ids``, the router's width under
``published``; ``vocab_size`` the rows held), and ``seq_len``,
``batch_size``, ``param_dtype``, ``attention`` (``flash`` or
``einsum``), ``expert_capacity``, ``remat``, ``optimizer`` (``name``
``adamw``, ``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay``).
"""

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.keys import seed_key
from torchsnapshot_tpu import PytreeStateful, StateDict
from torchsnapshot_tpu.models import nemotron_h as nh

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_STATE_KEYS = {"params", "master", "opt"}


def model_config(config: Dict[str, Any]) -> nh.NemotronHConfig:
    """The program's configuration from the file's published keys."""
    if config.get("mesh"):
        raise ValueError("nemotron_h_adam runs one chip's share: mesh must be null")
    if config["mamba_hidden_act"] != "silu" or config["mlp_hidden_act"] != "relu2":
        raise ValueError("models/nemotron_h.py has silu in Mamba and relu2 in experts")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("models/nemotron_h.py routes over one group of experts")
    if len(config["expert_ids"]) != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held: one id each")
    published = config["published"]
    return nh.NemotronHConfig(
        hidden_size=config["hidden_size"],
        pattern=config["hybrid_override_pattern"][: config["layers_held"]],
        vocab_size=config["vocab_size"],
        num_hidden_layers=config["num_hidden_layers"],
        norm_eps=config["norm_eps"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_state_size=config["ssm_state_size"],
        n_groups=config["n_groups"],
        conv_kernel=config["conv_kernel"],
        chunk_size=config["chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        time_step_limit=tuple(
            math.inf if v is None else v
            for v in config.get("time_step_limit", (0.0, None))
        ),
        n_routed_experts=published["n_routed_experts"],
        expert_ids=tuple(config["expert_ids"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"
        ],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        expert_capacity=config.get("expert_capacity", 0),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        flash_attention=config["attention"] == "flash",
        dtype=_DTYPES[config["param_dtype"]],
        remat=config.get("remat", True),
    )


class NemotronHAdamJob:
    """The state, the jitted donating step and the token feed."""

    def __init__(self, config: Dict[str, Any], devices: List[Any], seed: int) -> None:
        optimizer = dict(config["optimizer"])
        if optimizer.pop("name") != "adamw":
            raise ValueError("models/nemotron_h.py has AdamW only")
        self.config = config
        self.devices = devices
        self.seed = int(seed)
        self.cfg = cfg = model_config(config)
        self.hp = hp = nh.AdamW(**optimizer)
        self.batch, self.seq_len = int(config["batch_size"]), int(config["seq_len"])
        self.shapes = jax.eval_shape(lambda key: nh.init_state(cfg, key), seed_key(0))
        self.state_bytes = sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(self.shapes)
        )
        self._here = jax.sharding.SingleDeviceSharding(devices[0])
        self._step = jax.jit(
            lambda state, tokens: nh.adamw_train_step(state, tokens, cfg, hp),
            donate_argnums=(0,),
        )
        self._tokens = jax.jit(
            lambda key, step: jax.random.randint(
                jax.random.fold_in(key, step),
                (self.batch, self.seq_len),
                0,
                cfg.vocab_size,
            ),
            out_shardings=self._here,
        )
        self._token_key = jax.random.fold_in(seed_key(self.seed), 1)
        self._zeros = None

    # ------------------------------------------------------------- state

    def init_state(self):
        """The whole state in one jitted call, made where it lives."""
        cfg = self.cfg
        make = jax.jit(lambda key: nh.init_state(cfg, key), out_shardings=self._here)
        return make(jax.random.fold_in(seed_key(self.seed), 0))

    def template(self, layout: Optional[Dict[str, int]] = None):
        """A restore target that shares no bit with any saved state."""
        if layout:
            raise ValueError("one chip's share has one layout: check_layout is null")
        if self._zeros is None:
            shapes = self.shapes
            self._zeros = jax.jit(
                lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes),
                out_shardings=self._here,
            )
        return self._zeros()

    def tokens(self, step: int) -> jax.Array:
        """The batch of step ``step``: a function of seed and step alone,
        ids drawn from the vocabulary rows held."""
        return self._tokens(self._token_key, np.uint32(step))

    def train_step(self, state, step: int):
        """One step, ended as real loops end it: fenced, loss fetched."""
        state, loss = self._step(state, self.tokens(step))
        jax.block_until_ready(loss)
        return state, float(loss)

    # --------------------------------------------------------- app state

    def app_state(self, tree, step: int) -> Dict[str, Any]:
        """What goes to ``async_save`` / ``restore``. ``tree`` is the
        state, or any other tree of arrays (the warm-up's flat dict),
        which goes whole under ``model``."""
        progress = StateDict(step=step)
        if not (isinstance(tree, dict) and set(tree) == _STATE_KEYS):
            return {"model": PytreeStateful(tree), "progress": progress}
        return {
            "model": PytreeStateful(
                {"params": tree["params"], "master": tree["master"]}
            ),
            "optimizer": PytreeStateful(tree["opt"], convert=True),
            "progress": progress,
        }

    @staticmethod
    def state_of(app_state: Dict[str, Any]):
        model = app_state["model"].tree
        return {
            "params": model["params"],
            "master": model["master"],
            "opt": app_state["optimizer"].tree,
        }

    @staticmethod
    def step_of(app_state: Dict[str, Any]) -> int:
        return app_state["progress"]["step"]


def make_job(config: Dict[str, Any], devices: List[Any], seed: int) -> NemotronHAdamJob:
    return NemotronHAdamJob(config, devices, seed)
