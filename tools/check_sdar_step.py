"""One step of the ``mixed_adamw`` job over ``models/sdar.py`` at the
published widths and the cell's sizes against the plain reference, on
the chip: the block-diffusion loss, and the gradient of every leaf by
cosine and by norm.

    chiprun -- python3 tools/check_sdar_step.py [--seed N]

The reference (``perfbench/reference_sdar.py``) computes the program's
weights in float32 under ``jax.default_matmul_precision("highest")``,
layer by layer with each layer, each expert of the loop and each block
of 256 queries recomputed in the backward pass, so that it fits; it
draws the step's noise from the step's key as the program does, builds
the doubled sequence and masks the scores from (half, block) of each
token. Against it, four readings of the program, and the script exits 0
only if all four come out as they have to:

- ``float32``: the program computing in float32 under "highest". The
  same mathematics in another order (a fused projection, gathered
  experts, flash attention with the mask as a predicate in a tile and a
  skip of tiles, the head on the noised half alone), so this one is
  tight: it shows that what runs on the chip is the reference's model.
- ``bfloat16``: the program as the configuration states it. Within the
  limits below.
- ``bfloat16, the reference's choice of experts imposed``: the same
  bfloat16 program with every layer's router made to choose the top k
  the reference chose on its own float32 path (through ``held_gates``'
  bias, which enters the choice alone; the weights stay the program's
  own scores). What is left is the rounding alone, so this reading is
  held on EVERY leaf, and it is what shows where the self-routed
  reading's outliers come from.
- ``float8_weights``: the bfloat16 program on weights rounded to
  float8_e4m3 (the nearest precision below): has to FAIL the limits, by
  at least one of them, or the limits could not tell precisions apart.

It also counts, in every layer, for how many (row, held expert) pairs
the program routes otherwise than the reference (PR 28: at random
weights an expert whose token set changes by a share f moves its
gradient by about sqrt(2 f), which is most of what the expert leaves
read), how many of those lie on the five rows the objective weighs most
(1 / t), and the share of the squared weight routed here that is routed
otherwise: with a forward of its own, compiled apart from the step (see
below for what that cannot see).

Limits, each between two readings with room on both sides (`PERF.md` §6,
my chip runs, PR 36; seeds 2147490101 and 2147490102, reference loss
9.809237 and 10.574909, 1953 and 2090 of 4096 tokens masked). The
readings, first seed / second. Computing in float32 the program reads
loss 0 / 9.0e-8 relative, routes no (row, held expert) pair of
1101-21235 a layer otherwise, and its leaves read a difference's norm
of at most 4.0e-5 / 9.4e-5 of the reference's. In bfloat16 it reads loss
1.0e-5 / 7.7e-6, routes 21-153 pairs a layer otherwise (0.3-2.2 %), a
median leaf at 0.0186 / 0.0200 and a worst leaf at cosine 0.9983, norm
0.059 (a router) / **cosine 0.9318, norm 0.404** (the last layer's
``gate_up``, with its ``down``, ``router`` and ``mlp_norm`` at
0.36-0.37 and every other leaf at 0.090 or better). The float8-weights
control reads loss 4.1e-4 / 5.1e-4, a median leaf at 0.180 / 0.195, its
best leaf at 0.099 / 0.107 and its worst at cosine 0.931, norm 0.426 /
0.734, 0.748.

**Where the second seed's four outliers come from: the choice of
experts, shown and not guessed.** With the reference's choice imposed
the same bfloat16 program reads, on that seed, the last layer's
``gate_up`` at 0.0085 (from 0.404), ``down`` 0.0083, ``mlp_norm`` 0.0079,
``router`` 0.0070, and its worst leaf of all 69 at cosine 0.99976, norm
0.0218 (an attention leaf); on the first seed 0.99979 / 0.0204; median
0.0083 / 0.0084. So every leaf's arithmetic is the reference's to two
per cent, and what the self-routed reading adds is which experts a row
goes to. Which row, on that seed: the second heaviest (row 6742 of the
doubled sequence, weight 1 / t = 69.05, 15 % of the step's squared
weights and 18.6 % of those the last layer routes to the experts held:
sqrt(0.186) = 0.43). Read out of the step's own program by a callback
in ``held_gates`` (a scratch script, `PERF.md` §6): ONE OF THE TWO
PASSES the step makes over the last layer, the forward and the
recomputation its backward pass makes, sends that row to a held expert
otherwise than the reference, and the two passes differ from each other
in 0-129 pairs a layer (XLA need not round two bfloat16 computations
alike). **The count this file prints cannot see that**: it runs a
forward compiled apart from the step, which on that seed routes the
five heaviest rows as the reference does in every layer (0.05-0.5 % of
the squared weight routed otherwise a layer), and differs from the
step's own passes in 25-153 pairs a layer. It says how often a bfloat16
program of this model chooses otherwise, not what the step chose. The
last layer's expert side is where a row's choice acts undiluted: no
later layer mixes what an expert returned, and the control reads those
four leaves at 0.24-0.52 on every seed.

- loss: 7e-5 relative: 7 times the larger bfloat16 reading, a sixth of
  the smaller control reading of those two seeds. It does not tell
  precisions apart on every seed: on a third, 2147490103, the control's
  loss read 2.1e-5 (bfloat16 6.7e-6) and the control failed by the
  limits below, by one of the check's limits and not by each.
- the median over the leaves of the difference's norm: at most 0.06
  (3 times bfloat16's 0.020, a third of the control's 0.180; third
  seed 0.0177 against 0.183).
- leaves with cosine >= 0.995 and norm <= 0.10: at least 85 % of them
  (bfloat16: 69 and 65 of 69, third seed 67; the control: 1 and 0 of
  69, third seed 3).
- the worst leaf where the program chooses its experts itself, the last
  layer's four expert-side leaves left out: cosine >= 0.98, norm <= 0.20
  (bfloat16: 0.9983 / 0.059, 0.9969 / 0.090, third seed 0.9951 / 0.102:
  twice the largest; the control's worst outside those four: 0.9421 /
  0.426, 0.7343 / 0.748, third seed 0.9157 / 0.452: it fails this limit
  on every seed, by a factor of two).
- the worst leaf with the reference's choice imposed, no leaf left out:
  cosine >= 0.998, norm <= 0.06 (read 0.99976 / 0.0218 and 0.99979 /
  0.0204: 3 times the larger norm, 8 times the larger 1 - cosine; the
  control's BEST leaf reads 0.099 / 0.107, so a program rounded one
  precision lower fails it on every leaf). The loss, the median and
  the share of leaves are held in this reading too.
- float32 reading: difference's norm <= 1e-3 on every leaf (10 times the
  largest reading, a fifth of the smallest that bfloat16 reads on any
  leaf, 0.0045). No pair was routed otherwise in float32 in either
  seed's six layers; one row routed otherwise at a tie that
  reassociation decides would move a layer's expert leaves by a few per
  cent and fail this limit: the count is printed beside it.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _CHECKOUT)

LOSS_REL = 7e-5
MEDIAN_REL_L2 = 0.06
LEAF_COSINE = 0.995
LEAF_REL_L2 = 0.10
LEAVES_WITHIN = 0.85  # the share of leaves that has to keep both leaf limits
WORST_COSINE = 0.98  # every leaf, but for the last layer's expert side ...
WORST_REL_L2 = 0.20  # ... where the program chooses its experts itself
IMPOSED_COSINE = 0.998  # every leaf, none left out, under the reference's choice
IMPOSED_REL_L2 = 0.06
F32_REL_L2 = 1e-3
CELL = "sdar-30b-a3b-ep8.warm_start"
ROUTED_LEAVES = ("['gate_up']", "['down']", "['router']")
EXPERT_SIDE = ROUTED_LEAVES + ("['mlp_norm']",)
HEAVIEST = 5  # tokens, by the objective's weight 1 / t


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=2147490101)
    parser.add_argument(
        "--config", help="a configuration file of the job, for a rehearsal "
        "at a toy size on the CPU (default: the cell's own)",
    )
    args = parser.parse_args(argv)

    from perfbench import manifest
    from perfbench import reference_sdar as ref
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_CHECKOUT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu.models import experts
    from torchsnapshot_tpu.models import sdar

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    config = dict(cell.config)
    if args.config:
        with open(args.config) as f:
            config = json.load(f)
    job = manifest.load_module(cell.job_path).make_job(
        config, jax.devices()[:1], args.seed
    )
    cfg = job.cfg
    params = jax.jit(
        lambda key: jax.tree.map(
            lambda w: w.astype(cfg.dtype), sdar.init_master(cfg, key)
        )
    )(jax.random.key(args.seed & 0x7FFFFFFF))
    tokens, key = job.tokens(0), job.step_key(0)
    ref_config = dict(config, num_experts=cfg.num_experts, query_block=256)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(path) for path, _ in flat]

    def is_routed(name):
        return "['layers']" in name and name.endswith(ROUTED_LEAVES)

    last_expert_side = [
        n for n in names
        if n.startswith(f"['layers'][{cfg.layers - 1}]") and n.endswith(EXPERT_SIDE)
    ]

    def precision(highest):
        if highest:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()

    def say(what, began):
        print(f"{what} in {time.monotonic() - began:.1f} s", flush=True)

    def to_host(grads):
        return [np.asarray(g, np.float32).ravel() for g in jax.tree.leaves(grads)]

    # ---- the reference: loss, and the gradient of every leaf
    began = time.monotonic()
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    want_loss, want = jax.jit(
        jax.value_and_grad(lambda p: ref.loss(p, tokens, key, ref_config, remat=True))
    )(params32)
    want_loss, want = float(want_loss), to_host(want)
    _, masked, _ = ref.noise(tokens, key, ref_config)
    say(
        f"reference: loss {want_loss:.6f}, {int(masked.sum())} of {masked.size} "
        f"tokens masked",
        began,
    )

    eps = cfg.rms_norm_eps
    xt, masked, t = sdar.noise(tokens, key, cfg)
    ids = jnp.concatenate([tokens, xt], axis=1)
    positions = jnp.tile(jnp.arange(tokens.shape[1]), 2)
    held_ids = jnp.asarray(cfg.expert_ids)
    # The objective's weight of every row of the doubled sequence: 1 / t
    # on a masked token's noised copy, nothing anywhere else.
    weight = jnp.concatenate(
        [jnp.zeros_like(t), jnp.where(masked, 1.0 / t, 0.0)], axis=1
    ).reshape(-1)
    heaviest = jnp.argsort(-weight)[:HEAVIEST]

    def reference_choice(params32):
        """A layer a boolean [rows, the router's experts]: the top k the
        reference chooses on its own float32 path."""
        with jax.default_matmul_precision("highest"):
            x32 = params32["embed"][ids]
            chosen = []
            for blk32 in params32["layers"]:
                x32 = x32 + ref.attention(
                    ref._rms_norm(x32, blk32["attn_norm"], eps), blk32, ref_config
                )
                h32 = ref._rms_norm(x32, blk32["mlp_norm"], eps)
                flat = h32.reshape(-1, h32.shape[-1])
                chosen.append(ref.expert_gates(flat, blk32, ref_config) > 0)
                x32 = x32 + ref.routed_experts(
                    flat, blk32, ref_config, ref_config["expert_ids"]
                ).reshape(h32.shape)
            return chosen

    began = time.monotonic()
    choice = jax.jit(reference_choice)(params32)
    jax.block_until_ready(choice)
    say("the reference's choice of experts, every layer", began)

    @contextlib.contextmanager
    def choosing(choice):
        """The program's routers made to choose what the reference chose
        (``held_gates``' bias enters the choice alone: 2 on the
        reference's top k puts them above every softmax score), their
        weights still the program's own scores. One call a layer, in the
        layers' order (each layer is traced once)."""
        real, layers = experts.held_gates, iter(choice)

        def held_gates(x, router, bias, routing):
            assert bias is None
            return real(x, router, 2.0 * next(layers).astype(jnp.float32), routing)

        experts.held_gates = held_gates
        try:
            yield
            assert next(layers, None) is None, "a layer did not ask for its gates"
        finally:
            experts.held_gates = real

    def reading(name, run_config, run_params, highest=False, imposed=False):
        began = time.monotonic()

        def loss_and_grads(p, t, k, choice):
            with precision(highest), (
                choosing(choice) if imposed else contextlib.nullcontext()
            ):
                return jax.value_and_grad(sdar.loss_fn)(p, t, k, run_config)

        loss, grads = jax.jit(loss_and_grads)(run_params, tokens, key, choice)
        loss, grads = float(loss), to_host(grads)
        rows = {"loss": {"program": loss, "reference": want_loss,
                         "relative": abs(loss - want_loss) / abs(want_loss)}}
        for leaf, g, w in zip(names, grads, want):
            g, w = g.astype(np.float64), w.astype(np.float64)
            rows[leaf] = {
                "cosine": float(g @ w / (np.linalg.norm(g) * np.linalg.norm(w))),
                "relative_l2": float(np.linalg.norm(g - w) / np.linalg.norm(w)),
            }
        worst_cos = min(names, key=lambda n: rows[n]["cosine"])
        worst_l2 = max(names, key=lambda n: rows[n]["relative_l2"])
        say(
            f"{name}: loss {loss:.6f}; worst cosine {rows[worst_cos]['cosine']:.5f} "
            f"{worst_cos}; worst norm {rows[worst_l2]['relative_l2']:.5f} {worst_l2}",
            began,
        )
        return rows

    def routed_otherwise(run_config, run_params, highest=False):
        """A layer: the (row, held expert) pairs the program routes
        otherwise than the reference, those the reference routes, how
        many of the former lie on the ``HEAVIEST`` rows by the
        objective's weight, and the share of the squared weight routed
        here that is routed otherwise."""
        began = time.monotonic()

        def count(run_params, choice):
            x = run_params["embed"][ids]
            rows = []
            for blk, chosen in zip(run_params["layers"], choice):
                x = x + sdar.attention(
                    sdar.rms_norm(x, blk["attn_norm"], eps), blk, positions, run_config
                )
                h = sdar.rms_norm(x, blk["mlp_norm"], eps)
                flat = h.reshape(-1, h.shape[-1])
                _, mine = experts.held_gates(
                    flat, blk["router"], None, run_config.routing
                )
                theirs = chosen[:, held_ids]
                differs = mine != theirs
                squared = (weight * weight)[:, None]
                rows.append(
                    jnp.stack(
                        [
                            jnp.sum(differs),
                            jnp.sum(theirs),
                            jnp.sum(differs[heaviest]),
                            jnp.sum(squared * differs)
                            / jnp.sum(squared * (mine | theirs)),
                        ]
                    ).astype(jnp.float32)
                )
                x = x + sdar.routed_experts(flat, blk, run_config).reshape(h.shape)
            return jnp.stack(rows)

        def run(run_params, choice):
            with precision(highest):
                return count(run_params, choice)

        counted = np.asarray(jax.jit(run)(run_params, choice))
        say("pairs routed otherwise counted", began)
        return {
            "differing_a_layer": [int(v) for v in counted[:, 0]],
            "routed_a_layer": [int(v) for v in counted[:, 1]],
            "differing_on_the_heaviest_rows_a_layer": [int(v) for v in counted[:, 2]],
            "share_of_squared_weight_routed_otherwise_a_layer": [
                float(v) for v in counted[:, 3]
            ],
        }

    f32_config = dataclasses.replace(cfg, dtype=jnp.float32)
    readings = {"float32": reading("float32", f32_config, params32, highest=True)}
    routing = {"float32": routed_otherwise(f32_config, params32, highest=True)}
    del params32
    readings["bfloat16"] = reading("bfloat16", cfg, params)
    routing["bfloat16"] = routed_otherwise(cfg, params)
    readings["bfloat16_reference_choice"] = reading(
        "bfloat16, the reference's choice of experts imposed", cfg, params, imposed=True
    )
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype) if p.ndim >= 2 else p,
        params,
    )
    readings["float8_weights"] = reading("float8_weights", cfg, rounded)
    del rounded

    def median_l2(rows):
        return float(np.median([rows[n]["relative_l2"] for n in names]))

    def share_within(rows):
        return sum(
            rows[n]["cosine"] >= LEAF_COSINE and rows[n]["relative_l2"] <= LEAF_REL_L2
            for n in names
        ) / len(names)

    def worst(rows, but=()):
        """The lowest cosine and the largest norm over the leaves."""
        kept = [n for n in names if n not in but]
        return (
            min(rows[n]["cosine"] for n in kept),
            max(rows[n]["relative_l2"] for n in kept),
        )

    def as_stated(rows, but=(), limits=(WORST_COSINE, WORST_REL_L2)):
        cosine, rel_l2 = worst(rows, but)
        return (
            rows["loss"]["relative"] <= LOSS_REL
            and median_l2(rows) <= MEDIAN_REL_L2
            and share_within(rows) >= LEAVES_WITHIN
            and cosine >= limits[0]
            and rel_l2 <= limits[1]
        )

    f32 = readings["float32"]
    verdicts = {
        "float32": f32["loss"]["relative"] <= LOSS_REL
        and all(f32[n]["relative_l2"] <= F32_REL_L2 for n in names),
        "bfloat16": as_stated(readings["bfloat16"], but=last_expert_side),
        "bfloat16_reference_choice": as_stated(
            readings["bfloat16_reference_choice"],
            limits=(IMPOSED_COSINE, IMPOSED_REL_L2),
        ),
        "float8_weights_fails": not as_stated(
            readings["float8_weights"], but=last_expert_side
        ),
    }
    ok = all(verdicts.values())

    def spread(rows, key, routed):
        values = [rows[n][key] for n in names if is_routed(n) == routed]
        return [min(values), max(values)]

    summary = {
        name: {
            "loss_relative": rows["loss"]["relative"],
            "median_relative_l2": median_l2(rows),
            "share_of_leaves_within": share_within(rows),
            "worst_cosine": worst(rows)[0],
            "worst_relative_l2": worst(rows)[1],
            "worst_but_last_expert_side": list(worst(rows, last_expert_side)),
            "last_expert_side": {
                n: [rows[n]["cosine"], rows[n]["relative_l2"]] for n in last_expert_side
            },
            "routed_leaves_relative_l2": spread(rows, "relative_l2", True),
            "other_leaves_relative_l2": spread(rows, "relative_l2", False),
        }
        for name, rows in readings.items()
    }
    device = jax.devices()[0]
    doc = {
        "ok": bool(ok), "seed": args.seed, "verdicts": verdicts, "leaves": len(names),
        "limits": {"loss_relative": LOSS_REL, "median_relative_l2": MEDIAN_REL_L2,
                   "leaf_cosine": LEAF_COSINE, "leaf_relative_l2": LEAF_REL_L2,
                   "share_of_leaves_within": LEAVES_WITHIN,
                   "worst_cosine": WORST_COSINE, "worst_relative_l2": WORST_REL_L2,
                   "imposed_worst_cosine": IMPOSED_COSINE,
                   "imposed_worst_relative_l2": IMPOSED_REL_L2,
                   "float32_relative_l2": F32_REL_L2},
        "reference_loss": want_loss,
        "summary": summary,
        "routed_otherwise": routing,
        "heaviest_weights": [float(w) for w in np.asarray(weight[heaviest])],
        "device": {"platform": device.platform, "kind": device.device_kind},
    }
    out = os.path.join(_CHECKOUT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"sdar_step_check-{args.seed}.json"), "w") as f:
        json.dump(dict(doc, readings=readings), f, indent=1)
    print(json.dumps(doc), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
