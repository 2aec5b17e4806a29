"""One step of the ``mixed_adamw`` job over ``models/lfm2_moe.py`` at the
published widths and the cell's sizes against the plain reference, on
the chip: the loss, the gradient of every leaf by cosine and by norm,
and the expert biases after the step.

    python3 tools/check_lfm2_step.py [--seeds N [N ...]]   # on the chip

The reference (``perfbench/reference_lfm2.py``) computes the program's
weights in float32 under ``jax.default_matmul_precision("highest")``,
layer by layer with each layer, each expert of the loop and each block
of 512 queries recomputed in the backward pass, so that it fits. Both
route with the same expert biases, drawn uniformly from [-0.01, 0.01]
(what ten steps of the update could have given), so that the bias's
part in the choice is exercised. Against the reference, five readings
of the program, and the script exits 0 only if all five come out as
they have to, on every seed:

- ``float32``: the program computing in float32 under "highest". The
  same mathematics in another order (a fused projection, batched
  experts, the convolution's taps, flash attention at head 64, the tied
  head as an einsum), so this one is tight: it shows that what runs on
  the chip is the reference's model. Its biases after the step equal
  the reference's on every expert.
- ``bfloat16``: the program as the configuration states it. Within the
  limits below.
- ``bfloat16, the reference's choice of experts imposed``: the same
  bfloat16 program with every sparse layer made to choose the top k the
  reference chose on its own float32 path (through the expert bias,
  which enters the choice alone: 2 on the reference's top k, per token,
  puts them above every sigmoid score; the weights stay the program's
  own scores). What is left is the rounding alone, so its worst leaf
  is held tighter; its loads, and so its biases after the step, are
  the reference's.
- ``float8_weights``: the bfloat16 program on weights rounded to
  float8_e4m3 (the nearest precision below), choosing its experts
  itself and, as a fifth reading, with the reference's choice imposed:
  each has to FAIL the limits it is held to, by at least one of them,
  or the limits could not tell precisions apart.

Each reading also counts, a sparse layer, how far its loads lie from
the reference's (the sum over the router's experts of the difference in
tokens), and the experts whose bias after the step differs from the
reference's (``update_bias``: the rate towards the mean load) by more
than half the rate, that is, moved otherwise. In bfloat16 a token near
a tie between two experts' scores may choose otherwise, which moves two
loads by one and can flip an expert whose load sits at the mean; those
counts are printed, and held in the float32 and imposed readings alone.

Limits, each set between the bfloat16 readings and the float8 control's
on a TPU v5e (seeds 2147901101, 2147901203 and 2147901307, then
2147904211 and 2147904223 under these limits; every leaf counted, none
left out):

- loss: 1e-4 relative. The bfloat16 program read 7.5e-7 to 3.9e-5 on
  its own choice and 2.8e-7 to 1.3e-5 on the reference's; the float8
  control read 5.3e-5 to 1.6e-4 on its own choice and 1.7e-4 to 1.8e-4
  on the reference's. The mean over 8192 tokens averages the rounding
  away, so the loss alone does not tell the two precisions apart here:
  this limit keeps 2.5 times the largest bfloat16 reading and catches a
  wrong model, and the limits on the leaves below tell the precisions
  apart.
- the median over the leaves of the difference's norm: at most 0.10
  (bfloat16 0.039 to 0.042 on its own choice, 0.016 on the
  reference's; float8 0.31 and 0.25).
- leaves with cosine >= 0.995 and norm <= 0.10: at least half of them.
  The bfloat16 program on its own choice keeps 35 of the 51 on every
  seed: the 16 it does not are the router and the expert side of each
  sparse layer, where about 100 of the 32768 choices a layer go to
  another expert. On the reference's choice it keeps all 51; the float8
  control keeps one on either choice.
- the worst leaf, on the program's own choice: cosine >= 0.92 and norm
  <= 0.40 (bfloat16's worst is a router, at 0.974 to 0.979 and 0.20 to
  0.23; float8's at 0.72 to 0.74 and 0.71 to 0.74). One minus each
  limit, and the norm, lie about three times from each side's reading.
- the worst leaf with the reference's choice imposed: cosine >= 0.998,
  norm <= 0.06 (bfloat16 0.9995 and 0.032 at worst, where only the
  rounding is left; float8 0.930 to 0.946 and 0.33 to 0.37).
- float32 reading: difference's norm <= 1e-3 on every leaf (it read
  3.5e-5 at most; bfloat16's median leaf, the precision below, 0.016 on
  the reference's choice), and every bias after the step equal to the
  reference's.
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

LOSS_REL = 1e-4
MEDIAN_REL_L2 = 0.10
LEAF_COSINE = 0.995
LEAF_REL_L2 = 0.10
LEAVES_WITHIN = 0.5  # the share of leaves that has to keep both leaf limits
WORST_COSINE = 0.92  # every leaf, where the program chooses its experts itself
WORST_REL_L2 = 0.40
IMPOSED_COSINE = 0.998  # every leaf, under the reference's choice
IMPOSED_REL_L2 = 0.06
F32_REL_L2 = 1e-3
BIAS_SPREAD = 0.01  # the biases both route with: uniform on [-0.01, 0.01]
CELL = "lfm2-8b-a1b-ep4.save_in_loop"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seeds", type=int, nargs="+", default=[2147810101])
    parser.add_argument(
        "--config", help="a configuration file of the job, for a rehearsal "
        "at a toy size on the CPU (default: the cell's own)",
    )
    args = parser.parse_args(argv)

    from perfbench import manifest
    from perfbench import reference_lfm2 as ref
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_CHECKOUT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu.models import lfm2_moe as lfm2

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    config = dict(cell.config)
    if args.config:
        with open(args.config) as f:
            config = json.load(f)
    job = manifest.load_module(cell.job_path).make_job(config, jax.devices()[:1], 0)
    cfg = job.cfg
    ref_config = dict(config, query_block=512)
    eps = cfg.norm_eps

    def say(what, began):
        print(f"{what} in {time.monotonic() - began:.1f} s", flush=True)

    def to_host(grads):
        return [np.asarray(g, np.float32).ravel() for g in jax.tree.leaves(grads)]

    def precision(highest):
        if highest:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()

    # ---- compiled once, for every seed
    def ref_step(params32, bias, tokens):
        return jax.value_and_grad(
            lambda p: ref.loss(p, bias, tokens, ref_config, remat=True), has_aux=True
        )(params32)

    def reference_choice(params32, bias, tokens):
        """A sparse layer a float32 [tokens, router width]: 1 on the top
        k the reference chooses on its own float32 path."""
        with jax.default_matmul_precision("highest"):
            x = params32["embed"][tokens]
            biases = iter(bias)
            chosen = []
            for (kind, sparse), blk in zip(ref.layer_kinds(ref_config), params32["layers"]):
                b = next(biases) if sparse else None
                if sparse:
                    h = ref._rms_norm(x, blk["op_norm"], eps)
                    op = ref.short_conv if kind == "conv" else ref.attention
                    h2 = ref._rms_norm(x + op(h, blk, ref_config), blk["ffn_norm"], eps)
                    flat = h2.reshape(-1, h2.shape[-1])
                    chosen.append(ref.expert_choice(flat, blk["router"], b, ref_config)[1])
                x, _ = ref.layer(x, blk, b, kind, ref_config)
            return [c.astype(jnp.float32) for c in chosen]

    def program_step(run_config, highest):
        def step(params, bias, tokens):
            with precision(highest):
                return jax.value_and_grad(lfm2.loss_fn, has_aux=True)(
                    params, bias, tokens, run_config
                )

        return jax.jit(step)

    ref_step = jax.jit(ref_step)
    reference_choice = jax.jit(reference_choice)
    f32_config = dataclasses.replace(cfg, dtype=jnp.float32)
    f32_step = program_step(f32_config, highest=True)
    bf16_step = program_step(cfg, highest=False)

    docs, ok = [], True
    for seed in args.seeds:
        params = jax.jit(
            lambda key: jax.tree.map(lambda w: w.astype(cfg.dtype), lfm2.init_master(cfg, key))
        )(jax.random.key(seed & 0x7FFFFFFF))
        bias = [
            jax.random.uniform(
                jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), 7 + i),
                (cfg.num_experts,), minval=-BIAS_SPREAD, maxval=BIAS_SPREAD,
            )
            for i, _ in enumerate(cfg.sparse_layers)
        ]
        tokens = lfm2.draw_tokens(
            jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), 1),
            (job.batch, job.seq_len), cfg,
        )
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        names = [jax.tree_util.keystr(path) for path, _ in flat]

        began = time.monotonic()
        params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        (want_loss, want_loads), want = ref_step(params32, bias, tokens)
        want_loss, want = float(want_loss), to_host(want)
        want_bias = ref.update_bias(bias, want_loads, cfg.expert_bias_rate)
        choice = reference_choice(params32, bias, tokens)
        jax.block_until_ready(choice)
        say(f"seed {seed}: reference loss {want_loss:.6f}, and its choice of experts", began)

        def reading(name, step, run_params, run_bias):
            began = time.monotonic()
            (loss, loads), grads = step(run_params, run_bias, tokens)
            loss, grads = float(loss), to_host(grads)
            moved = lfm2.update_expert_bias(bias, loads, cfg.expert_bias_rate)
            rows = {"loss": {"program": loss, "reference": want_loss,
                             "relative": abs(loss - want_loss) / abs(want_loss)}}
            for leaf, g, w in zip(names, grads, want):
                g, w = g.astype(np.float64), w.astype(np.float64)
                rows[leaf] = {
                    "cosine": float(g @ w / (np.linalg.norm(g) * np.linalg.norm(w))),
                    "relative_l2": float(np.linalg.norm(g - w) / np.linalg.norm(w)),
                }
            # A bias differs where it moved the other way, or not at all
            # where the reference's moved: by more than half the rate
            # (both sides round ``b + rate`` in float32, the reference
            # from float64, so equal updates may differ in the last bit).
            rows["biases_differing_a_layer"] = [
                int(np.sum(np.abs(np.asarray(m, np.float64) - b) > cfg.expert_bias_rate / 2))
                for m, b in zip(moved, want_bias)
            ]
            rows["load_moved_a_layer"] = [
                int(np.sum(np.abs(np.asarray(n) - np.asarray(w))))
                for n, w in zip(loads, want_loads)
            ]
            worst_cos = min(names, key=lambda n: rows[n]["cosine"])
            worst_l2 = max(names, key=lambda n: rows[n]["relative_l2"])
            say(
                f"{name}: loss {loss:.6f}; worst cosine {rows[worst_cos]['cosine']:.5f} "
                f"{worst_cos}; worst norm {rows[worst_l2]['relative_l2']:.5f} {worst_l2}; "
                f"biases differing {rows['biases_differing_a_layer']}",
                began,
            )
            return rows

        readings = {"float32": reading("float32", f32_step, params32, bias)}
        del params32
        readings["bfloat16"] = reading("bfloat16", bf16_step, params, bias)
        imposed = [2.0 * c for c in choice]
        readings["bfloat16_reference_choice"] = reading(
            "bfloat16, the reference's choice of experts imposed", bf16_step, params, imposed
        )
        rounded = jax.tree.map(
            lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype) if p.ndim >= 2 else p,
            params,
        )
        readings["float8_weights"] = reading("float8_weights", bf16_step, rounded, bias)
        readings["float8_weights_reference_choice"] = reading(
            "float8_weights, the reference's choice of experts imposed",
            bf16_step, rounded, imposed,
        )
        del rounded, params

        def median_l2(rows):
            return float(np.median([rows[n]["relative_l2"] for n in names]))

        def share_within(rows):
            return sum(
                rows[n]["cosine"] >= LEAF_COSINE and rows[n]["relative_l2"] <= LEAF_REL_L2
                for n in names
            ) / len(names)

        def worst(rows):
            """The lowest cosine and the largest norm over the leaves."""
            return (
                min(rows[n]["cosine"] for n in names),
                max(rows[n]["relative_l2"] for n in names),
            )

        def as_stated(rows, limits=(WORST_COSINE, WORST_REL_L2)):
            cosine, rel_l2 = worst(rows)
            return (
                rows["loss"]["relative"] <= LOSS_REL
                and median_l2(rows) <= MEDIAN_REL_L2
                and share_within(rows) >= LEAVES_WITHIN
                and cosine >= limits[0]
                and rel_l2 <= limits[1]
            )

        f32 = readings["float32"]
        imposed_rows = readings["bfloat16_reference_choice"]
        verdicts = {
            "float32": f32["loss"]["relative"] <= LOSS_REL
            and all(f32[n]["relative_l2"] <= F32_REL_L2 for n in names)
            and not any(f32["biases_differing_a_layer"]),
            "bfloat16": as_stated(readings["bfloat16"]),
            "bfloat16_reference_choice": as_stated(
                imposed_rows, limits=(IMPOSED_COSINE, IMPOSED_REL_L2)
            )
            and not any(imposed_rows["load_moved_a_layer"]),
            "float8_weights_fails": not as_stated(readings["float8_weights"]),
            "float8_weights_reference_choice_fails": not as_stated(
                readings["float8_weights_reference_choice"],
                limits=(IMPOSED_COSINE, IMPOSED_REL_L2),
            ),
        }
        ok = ok and all(verdicts.values())
        summary = {
            name: {
                "loss_relative": rows["loss"]["relative"],
                "median_relative_l2": median_l2(rows),
                "share_of_leaves_within": share_within(rows),
                "worst_cosine": worst(rows)[0],
                "worst_relative_l2": worst(rows)[1],
                "biases_differing_a_layer": rows["biases_differing_a_layer"],
                "load_moved_a_layer": rows["load_moved_a_layer"],
            }
            for name, rows in readings.items()
        }
        doc = {"seed": seed, "verdicts": verdicts, "leaves": len(names),
               "reference_loss": want_loss, "summary": summary}
        docs.append(doc)
        out = os.path.join(_CHECKOUT, "perfbench_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"lfm2_step_check-{seed}.json"), "w") as f:
            json.dump(dict(doc, readings=readings), f, indent=1)
        print(json.dumps(doc), flush=True)

    device = jax.devices()[0]
    print(json.dumps({
        "ok": bool(ok), "seeds": args.seeds,
        "verdicts": {d["seed"]: d["verdicts"] for d in docs},
        "limits": {"loss_relative": LOSS_REL, "median_relative_l2": MEDIAN_REL_L2,
                   "leaf_cosine": LEAF_COSINE, "leaf_relative_l2": LEAF_REL_L2,
                   "share_of_leaves_within": LEAVES_WITHIN,
                   "worst_cosine": WORST_COSINE, "worst_relative_l2": WORST_REL_L2,
                   "imposed_worst_cosine": IMPOSED_COSINE,
                   "imposed_worst_relative_l2": IMPOSED_REL_L2,
                   "float32_relative_l2": F32_REL_L2},
        "device": {"platform": device.platform, "kind": device.device_kind},
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
