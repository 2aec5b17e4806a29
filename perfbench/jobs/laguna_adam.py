"""Job ``laguna_adam``: one chip's share of a Laguna stack
(``models/laguna.py``: full and sliding-window attention layers of
different head counts, a dense and sparse SwiGLU feed-forward) under
mixed-precision AdamW, built from a configuration file with everything
made on the device from ``--seed``.

The saved state and its Statefuls are spelled as ``nemotron_h_adam``
spells them,

    {"params": the compute copies (``param_dtype``),
     "master": float32,
     "opt":    (Moments(mu, nu) in float32, an int32 count)}

14 bytes a parameter saved, 16 resident with a step's gradients; to
``CheckpointManager`` as ``model`` (params and master, plain
containers), ``optimizer`` (the tuple with its named tuple,
``convert=True``) and ``progress``.

The configuration's keys that are this job's own: the published keys of
the model's ``config.json`` (``layer_types``, ``mlp_layer_types`` and
``num_attention_heads_per_layer`` whole, of which the first
``layers_held`` entries are held; ``num_experts`` the experts held,
their ids under ``expert_ids``, the router's width under ``published``;
``vocab_size`` the rows held; ``rope_parameters`` by attention kind),
and ``seq_len``, ``batch_size``, ``param_dtype``, ``attention``
(``flash`` or ``einsum``), ``expert_capacity``, ``expert_dense_group``,
``remat``, ``optimizer``
(``name`` ``adamw``, ``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay``).
"""

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.keys import seed_key
from torchsnapshot_tpu import PytreeStateful, StateDict
from torchsnapshot_tpu.models import laguna as lg

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
_STATE_KEYS = {"params", "master", "opt"}


def _rope(entry: Dict[str, Any]) -> lg.Rope:
    """One entry of ``rope_parameters``."""
    if entry.get("rope_type", "default") == "default":
        return lg.Rope(
            theta=entry["rope_theta"],
            partial_rotary_factor=entry.get("partial_rotary_factor", 1.0),
        )
    if entry["rope_type"] != "yarn":
        raise ValueError("models/laguna.py has plain rotary and YaRN")
    return lg.Rope(
        theta=entry["rope_theta"],
        partial_rotary_factor=entry.get("partial_rotary_factor", 1.0),
        factor=entry["factor"],
        original_max_position_embeddings=entry.get("original_max_position_embeddings"),
        beta_fast=entry.get("beta_fast", 32),
        beta_slow=entry.get("beta_slow", 1),
        attention_factor=entry.get("attention_factor"),
    )


def model_config(config: Dict[str, Any]) -> lg.LagunaConfig:
    """The program's configuration from the file's published keys."""
    if config.get("mesh"):
        raise ValueError("laguna_adam runs one chip's share: mesh must be null")
    if config["attention_bias"] or config["tie_word_embeddings"]:
        raise ValueError("models/laguna.py has no bias and an untied head")
    if config["moe_apply_router_weight_on_input"] or not config["gating"]:
        raise ValueError(
            "models/laguna.py weights the experts' outputs and gates each head"
        )
    if len(config["expert_ids"]) != config["num_experts"]:
        raise ValueError("num_experts counts the experts held: one id each")
    held = config["layers_held"]
    return lg.LagunaConfig(
        hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"][:held]),
        mlp_layer_types=tuple(config["mlp_layer_types"][:held]),
        num_attention_heads_per_layer=tuple(
            config["num_attention_heads_per_layer"][:held]
        ),
        vocab_size=config["vocab_size"],
        rms_norm_eps=config["rms_norm_eps"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        sliding_window=config["sliding_window"],
        max_position_embeddings=config["max_position_embeddings"],
        rope_full=_rope(config["rope_parameters"][lg.FULL]),
        rope_sliding=_rope(config["rope_parameters"][lg.SLIDING]),
        flash_attention=config["attention"] == "flash",
        intermediate_size=config["intermediate_size"],
        num_experts=config["published"]["num_experts"],
        expert_ids=tuple(config["expert_ids"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config["shared_expert_intermediate_size"],
        moe_routed_scaling_factor=config["moe_routed_scaling_factor"],
        expert_capacity=config.get("expert_capacity", 0),
        expert_dense_group=config.get("expert_dense_group", 0),
        dtype=_DTYPES[config["param_dtype"]],
        remat=config.get("remat", True),
    )


class LagunaAdamJob:
    """The state, the jitted donating step and the token feed."""

    def __init__(self, config: Dict[str, Any], devices: List[Any], seed: int) -> None:
        optimizer = dict(config["optimizer"])
        if optimizer.pop("name") != "adamw":
            raise ValueError("models/laguna.py steps with AdamW only")
        self.config = config
        self.devices = devices
        self.seed = int(seed)
        self.cfg = cfg = model_config(config)
        self.hp = hp = lg.AdamW(**optimizer)
        self.batch, self.seq_len = int(config["batch_size"]), int(config["seq_len"])
        self.shapes = jax.eval_shape(lambda key: lg.init_state(cfg, key), seed_key(0))
        self.state_bytes = sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(self.shapes)
        )
        self._here = jax.sharding.SingleDeviceSharding(devices[0])
        self._step = jax.jit(
            lambda state, tokens: lg.adamw_train_step(state, tokens, cfg, hp),
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
        make = jax.jit(lambda key: lg.init_state(cfg, key), out_shardings=self._here)
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
        """What goes to ``save`` / ``restore``. ``tree`` is the state, or
        any other tree of arrays (a warm-up's flat dict), which goes
        whole under ``model``."""
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


def make_job(config: Dict[str, Any], devices: List[Any], seed: int) -> LagunaAdamJob:
    return LagunaAdamJob(config, devices, seed)
