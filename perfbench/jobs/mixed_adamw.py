"""Job ``mixed_adamw``: one chip's share of a model under mixed-precision
AdamW, over **the model module that the configuration names** under
``model`` (``torchsnapshot_tpu/models/<model>.py``), built from a
configuration file with everything made on the device from ``--seed``.

The saved state and its Statefuls are spelled as ``nemotron_h_adam`` and
``laguna_adam`` spell them,

    {"params": the compute copies (``param_dtype``),
     "master": float32,
     "opt":    (Moments(mu, nu) in float32, an int32 count)}

14 bytes a parameter saved, 16 resident with a step's gradients; to
``CheckpointManager`` as ``model`` (params and master, plain
containers), ``optimizer`` (the tuple with its named tuple,
``convert=True``) and ``progress``.

What this file asks of a model module, and nothing more (so that a later
``benchmark`` PR can point the two older configurations at it, once
their modules offer the same five names):

- ``config_from_published(config) -> cfg``: the program's configuration
  from the file's keys, refusing aloud what the model cannot run;
- ``init_state(cfg, key)``: the whole training state, jit-able;
- ``draw_tokens(key, shape, cfg)``: ids from the vocabulary rows held;
- ``adamw_train_step(state, tokens, key, cfg, hp) -> (state, loss)``:
  one step; ``key`` is the step's own (``fold_in`` of seed and step),
  for an objective that draws noise, and ignored by one that does not;
- ``AdamW``: the optimizer's hyperparameters.

The configuration's keys that are this job's own: ``model``; the
published keys of the model's ``config.json`` as the module's
``config_from_published`` reads them (``models/sdar.py``: ``num_experts``
the experts held, their ids under ``expert_ids``, the router's width
under ``published``; ``vocab_size`` the rows held; ``layers_held``), and
``seq_len`` (the tokens of a sequence; a block-diffusion step runs twice
as many positions), ``batch_size``, ``param_dtype``, ``attention``
(``flash`` or ``einsum``), ``expert_capacity``, ``expert_dense_group``,
``remat``, ``block_length``, ``optimizer`` (``name`` ``adamw``, ``lr``,
``b1``, ``b2``, ``eps``, ``weight_decay``).
"""

import importlib
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.keys import seed_key
from torchsnapshot_tpu import PytreeStateful, StateDict

_STATE_KEYS = {"params", "master", "opt"}


class MixedAdamWJob:
    """The state, the jitted donating step and the token feed."""

    def __init__(self, config: Dict[str, Any], devices: List[Any], seed: int) -> None:
        if config.get("mesh"):
            raise ValueError("mixed_adamw runs one chip's share: mesh must be null")
        optimizer = dict(config["optimizer"])
        if optimizer.pop("name") != "adamw":
            raise ValueError("mixed_adamw steps with AdamW only")
        # A parent of the PR that brought a model has no such module:
        # the import fails here, at once, before anything is built.
        self.model = model = importlib.import_module(
            "torchsnapshot_tpu.models." + config["model"]
        )
        self.config = config
        self.devices = devices
        self.seed = int(seed)
        self.cfg = cfg = model.config_from_published(config)
        self.hp = hp = model.AdamW(**optimizer)
        self.batch, self.seq_len = int(config["batch_size"]), int(config["seq_len"])
        self.shapes = jax.eval_shape(lambda key: model.init_state(cfg, key), seed_key(0))
        self.state_bytes = sum(
            int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(self.shapes)
        )
        self._here = jax.sharding.SingleDeviceSharding(devices[0])
        self._step = jax.jit(
            lambda state, tokens, key: model.adamw_train_step(state, tokens, key, cfg, hp),
            donate_argnums=(0,),
        )
        self._tokens = jax.jit(
            lambda key, step: model.draw_tokens(
                jax.random.fold_in(key, step), (self.batch, self.seq_len), cfg
            ),
            out_shardings=self._here,
        )
        self._token_key = jax.random.fold_in(seed_key(self.seed), 1)
        self._noise_key = jax.random.fold_in(seed_key(self.seed), 2)
        self._zeros = None

    # ------------------------------------------------------------- state

    def init_state(self):
        """The whole state in one jitted call, made where it lives."""
        model, cfg = self.model, self.cfg
        make = jax.jit(lambda key: model.init_state(cfg, key), out_shardings=self._here)
        return make(jax.random.fold_in(seed_key(self.seed), 0))

    def template(self, layout: Optional[Dict[str, int]] = None):
        """A restore target that shares no bit with any saved state: a
        zeroed state, whose moments and count are a fresh AdamW's."""
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

    def step_key(self, step: int) -> jax.Array:
        """The key of step ``step``'s noise: seed and step alone."""
        return jax.random.fold_in(self._noise_key, np.uint32(step))

    def train_step(self, state, step: int):
        """One step, ended as real loops end it: fenced, loss fetched."""
        state, loss = self._step(state, self.tokens(step), self.step_key(step))
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


def make_job(config: Dict[str, Any], devices: List[Any], seed: int) -> MixedAdamWJob:
    return MixedAdamWJob(config, devices, seed)
