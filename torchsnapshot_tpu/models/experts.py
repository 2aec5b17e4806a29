"""One chip's share of a mixture-of-experts layer: the router over all
of the layer's experts and the part of the result that the experts held
here give. What ``nemotron_h.py``, ``laguna.py`` and ``sdar.py`` run.

The layer is told which experts it holds (:class:`Routing`), scores
every token over all of them at the router's published width (by
sigmoid, or by softmax: ``sdar.py``), takes
each token's top k, and computes its own experts' part: what the absent
experts would have added is left out, as expert parallelism leaves it
to the other chips. No token is dropped.

The expert's body is the caller's: ``hidden(project)`` returns the held
experts' hidden activations, where ``project(w)`` multiplies the rows
an expert computes by that expert's slice of a stacked leaf ``w``
[held, d, f] (``relu(x W_up)^2`` of one leaf, ``silu(x W_gate) * x
W_up`` of two or of one fused leaf); the down projection [held, f, d]
is common to every body.
"""

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Routing:
    """How a token's experts are chosen and weighted, and which of them
    live here."""

    expert_ids: Tuple[int, ...]  # the experts held, in the stacked leaves' order
    top_k: int
    normalise: bool  # the chosen scores divided by their sum
    scaling_factor: float
    # Rows a held expert computes a step: 0 = every token (dense over the
    # held experts). Otherwise tokens are gathered into that many slots an
    # expert, and a step in which some expert is sent more falls back to
    # the dense computation, so that no token is ever dropped.
    capacity: int = 0
    # Held experts the dense computation runs at once: 0 = all of them in
    # one batched product. Otherwise it goes through them that many at a
    # time, each group recomputed in the backward pass, so that what it
    # holds live is one group's activations [group, tokens, f] and not
    # the layer's (32 experts x 8192 tokens x 1024 in float32 is 1 GiB a
    # tensor, several of them in the backward pass). Must divide the
    # number of experts held.
    dense_group: int = 0
    # How the router's logits become scores: "sigmoid" (each expert's
    # own), or "softmax" over all of the router's experts, in float32.
    scoring: str = "sigmoid"

    def __post_init__(self):
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring is sigmoid or softmax, not {self.scoring!r}")


def held_gates(x, router, bias, routing: Routing):
    """``[tokens, held experts]`` float32: the weight with which each
    held expert's result enters each token, 0 where the token's top k
    (over all the router's experts) does not name it; and, as booleans,
    which tokens are routed to which held expert. ``bias`` (or None) is
    added to the scores for the choice alone."""
    score = jax.nn.softmax if routing.scoring == "softmax" else jax.nn.sigmoid
    scores = score(
        jnp.einsum("td,de->te", x.astype(_F32), router.astype(_F32))
    )
    choice = scores if bias is None else scores + bias.astype(_F32)
    _, chosen = jax.lax.top_k(choice, routing.top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if routing.normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * routing.scaling_factor
    held = jnp.asarray(routing.expert_ids, chosen.dtype)
    hit = chosen[:, :, None] == held[None, None, :]  # [T, K, held]
    return jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1), jnp.any(hit, axis=1)


def experts_dense(x, gates, hidden: Callable, down, group: int = 0):
    """Every held expert on every token, weighted by its gate; ``group``
    of them at a time (:attr:`Routing.dense_group`), or all at once."""
    held = gates.shape[1]

    def these(of):
        """The part of the result of the experts that ``of`` picks out
        of a leaf stacked over the held ones."""
        h = hidden(lambda w: jnp.einsum("td,edf->etf", x, of(w)))
        h = h * of(gates.T)[:, :, None].astype(h.dtype)
        return jnp.einsum("etf,efd->td", h, of(down))

    if not group or group >= held:
        return these(lambda w: w)
    if held % group:
        raise ValueError(f"dense_group {group} does not divide {held} held experts")

    @jax.checkpoint
    def add_group(total, first):
        return total + these(
            lambda w: jax.lax.dynamic_slice_in_dim(w, first, group, axis=0)
        ), None

    total, _ = jax.lax.scan(add_group, jnp.zeros_like(x), jnp.arange(0, held, group))
    return total


def experts_gathered(x, gates, routed, hidden: Callable, down, capacity):
    """Each held expert on the tokens routed to it, gathered into
    ``capacity`` slots; exact when no expert is sent more than that."""
    t = x.shape[0]
    held = gates.shape[1]
    slot = jnp.where(routed, jnp.cumsum(routed, axis=0) - 1, capacity)
    expert = jnp.broadcast_to(jnp.arange(held), (t, held))
    token = jnp.broadcast_to(jnp.arange(t)[:, None], (t, held))
    # token_of[e, s]: the s-th token routed to e; t marks an empty slot.
    token_of = jnp.full((held, capacity), t, jnp.int32).at[expert, slot].set(
        token, mode="drop"
    )
    xg = jnp.take(x, token_of, axis=0, mode="fill", fill_value=0)
    gg = jnp.take_along_axis(
        jnp.pad(gates, [(0, 1), (0, 0)]).T, token_of, axis=1
    )
    h = hidden(lambda w: jnp.einsum("ecd,edf->ecf", xg, w))
    h = h * gg[:, :, None].astype(x.dtype)
    y = jnp.einsum("ecf,efd->ecd", h, down)
    return jnp.zeros_like(x).at[token_of.reshape(-1)].add(
        y.reshape(-1, x.shape[1]), mode="drop"
    )


def routed_experts(
    x, router, bias: Optional[jax.Array], hidden: Callable, down, routing: Routing
):
    """The held experts' part of the layer's result, ``x`` [tokens, d]."""
    gates, routed = held_gates(x, router, bias, routing)
    capacity = routing.capacity
    if not capacity or capacity >= x.shape[0]:
        return experts_dense(x, gates, hidden, down, routing.dense_group)
    return jax.lax.cond(
        jnp.max(jnp.sum(routed, axis=0)) <= capacity,
        lambda: experts_gathered(x, gates, routed, hidden, down, capacity),
        lambda: experts_dense(x, gates, hidden, down, routing.dense_group),
    )
