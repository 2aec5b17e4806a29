"""Stateful adapters for common training-state shapes.

The reference's ``Stateful`` protocol expects objects with
``state_dict``/``load_state_dict`` methods; JAX training code usually
holds bare pytrees (params dicts, optax states, flax TrainStates). These
adapters bridge the two without forcing users to write wrapper classes.
"""

from typing import Any, Callable, Dict, Optional

from .tree import from_state_dict, to_state_dict


class PytreeStateful:
    """Wraps a bare pytree so it participates in an app state.

    For plain-container pytrees (nested dict/list/tuple of arrays) the
    tree is passed through as-is; for arbitrary pytrees (optax NamedTuple
    states, flax structs) set ``convert=True`` to round-trip through
    plain containers while preserving the original structure on load.

    ::

        state = PytreeStateful({"params": params})
        Snapshot.take(path, {"train": state})
        ...
        Snapshot(path).restore({"train": state})
        params = state.tree["params"]
    """

    def __init__(self, tree: Any, convert: bool = False) -> None:
        self.tree = tree
        self._convert = convert

    def state_dict(self) -> Dict[str, Any]:
        if self._convert:
            return to_state_dict(self.tree)
        return self.tree

    def load_state_dict(self, state_dict: Dict[str, Any]) -> None:
        if self._convert:
            self.tree = from_state_dict(self.tree, state_dict)
        else:
            self.tree = state_dict

    def release_template(self) -> None:
        """Let go of the device buffers of the tree, keeping its
        structure: every ``jax.Array`` leaf becomes a
        ``jax.ShapeDtypeStruct`` of the same shape, dtype and sharding.

        ``restore`` calls this, once it has read where every leaf has to
        land, when the restored arrays would not fit on the device
        beside the template they replace (a state above half of HBM).
        Only references are dropped: an array that the caller still
        holds elsewhere stays alive and valid. If the restore then
        fails, the tree holds shapes, which a second ``restore`` takes
        as its target all the same.
        """
        import jax

        from ..io_preparer import abstract_of

        self.tree = jax.tree.map(abstract_of, self.tree)


class FnStateful:
    """Builds a Stateful from getter/setter callables — for state owned by
    an object you can't (or don't want to) subclass::

        FnStateful(lambda: trainer.get_state(), trainer.set_state)
    """

    def __init__(
        self,
        get_fn: Callable[[], Dict[str, Any]],
        set_fn: Callable[[Dict[str, Any]], None],
    ) -> None:
        self._get = get_fn
        self._set = set_fn

    def state_dict(self) -> Dict[str, Any]:
        return self._get()

    def load_state_dict(self, state_dict: Dict[str, Any]) -> None:
        self._set(state_dict)
