"""One step of the ``laguna_adam`` job at the published widths and the
cell's sizes against the plain reference, on the chip: the loss, and the
gradient of every leaf by cosine and by norm.

    chiprun -- python3 tools/check_laguna_step.py [--seed N]

The reference (``perfbench/reference_laguna.py``) computes the program's
weights in float32 under ``jax.default_matmul_precision("highest")``,
layer by layer with each layer, each expert of the loop and each block
of 256 queries recomputed in the backward pass, so that it fits. Against
it, three readings of the program (``models/laguna.py``), and the script
exits 0 only if all three come out as they have to:

- ``float32``: the program computing in float32 under "highest". The
  same mathematics in another order (fused projections, gathered
  experts, flash attention with its window as a block skip), so this one
  is tight: it shows that what runs on the chip is the reference's model.
- ``bfloat16``: the program as the configuration states it. Within the
  limits below.
- ``float8_weights``: the bfloat16 program on weights rounded to
  float8_e4m3 (the nearest precision below): has to FAIL the limits, by
  at least one of them, or the limits could not tell precisions apart.

It also counts, in every sparse layer, for how many (token, held expert)
pairs the program routes otherwise than the reference (PR 28: at random
weights an expert whose token set changes by a share f moves its
gradient by about sqrt(2 f), which is most of what the expert leaves
read).

Limits, each between two readings with room on both sides (`PERF.md` §6,
my chip runs, PR 33; seeds 2147484733 and 2147484734, reference loss
9.846060 and 9.819456). The readings, first seed / second: computing in
float32 the program reads loss 2.9e-7 / 5.8e-7 relative and routes 1, 0,
1, 1 / 4, 4, 1, 5 of 7958-9849 (token, held expert) pairs a layer
otherwise; its leaves read a difference's norm of 3e-6 to 2.1e-3 of the
reference's, but for the sparse layers' routed leaves (the experts' two
and the router: 0.0024-0.018 / 0.009-0.031) and their ``mlp_norm``, the
norm that feeds the router (0.0036 / 0.0078). In bfloat16 it reads loss
1.35e-5 / 6.9e-5, routes 167-324 / 192-279 pairs a layer otherwise
(2.0-3.3 %), and its worst leaves are the four routers, cosine
0.9705-0.9824 / 0.9685-0.9819, norm 0.187-0.243 / 0.190-0.251 (sqrt(2 x
0.033) = 0.257 is what a token set changed by 3.3 % gives), then the
experts' leaves 0.984-0.990 / 0.143-0.181; every other leaf 0.998 /
0.065 or better. The float8-weights control reads loss 1.01e-4 / 1.2e-5,
routers 0.723-0.763 / 0.686-0.748, experts 0.821-0.836 / 0.572-0.603,
14 / 14 of 60 leaves outside:

- gradients: cosine >= 0.93 and difference's norm <= 0.40 of the
  reference's on every leaf: what tells precisions apart. Wider than the
  Nemotron check's 0.97 / 0.25 because this model holds 32 experts a
  layer where that one holds 8, each seeing 256 tokens and not 384, so
  the same share of tokens routed otherwise moves a router's gradient
  further: the worst routers read 0.9705 / 0.243 and 0.9685 / 0.251.
  The control's routers and experts miss both by 0.2 and more.
- loss: 3e-4 relative, the limit of ``check_nemotron_h_step.py``, 4.3
  times the largest bfloat16 reading. The loss does not tell precisions
  apart here (the control read 1.0e-4 on one seed and 1.2e-5, under the
  bfloat16 program's own 6.9e-5, on the other: over 8191 positions the
  roundings cancel), so no limit lies between its two readings; the
  control is failed by the gradients' limits, by one of the check's
  limits and not by each.
- float32 reading: difference's norm <= 0.02 on every leaf (2.6 times
  the largest reading outside the routed leaves, a third of what
  bfloat16 reads on the same leaf, ``mlp_norm``) but the sparse layers'
  routed experts and routers, <= 0.08 (2.6 times the largest reading,
  half the smallest that bfloat16 reads there, 0.143; one token of 8192
  routed otherwise, at a tie that float32 reassociation decides, moves
  an expert's gradient by sqrt(2 / 256) = 9 % of its own).
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

LOSS_REL = 3e-4
GRAD_COSINE = 0.93
GRAD_REL_L2 = 0.40
F32_REL_L2 = 0.02
F32_REL_L2_EXPERTS = 0.08
CELL = "laguna-xs2-ep8.kill_resume"
ROUTED_LEAVES = ("['gate_up']", "['down']", "['router']")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=2147484733)
    parser.add_argument(
        "--config", help="a configuration file of the job, for a rehearsal "
        "at a toy size on the CPU (default: the cell's own)",
    )
    args = parser.parse_args(argv)

    from perfbench import manifest
    from perfbench import reference_laguna as ref
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_CHECKOUT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu.models import experts
    from torchsnapshot_tpu.models import laguna as lg

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
            lambda w: w.astype(cfg.dtype), lg.init_master(cfg, key)
        )
    )(jax.random.key(args.seed & 0x7FFFFFFF))
    tokens = job.tokens(0)
    held = config["layers_held"]
    ref_config = dict(
        config,
        layer_types=config["layer_types"][:held],
        mlp_layer_types=config["mlp_layer_types"][:held],
        num_attention_heads_per_layer=config["num_attention_heads_per_layer"][:held],
        num_experts=cfg.num_experts,
        query_block=256,
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    names = [jax.tree_util.keystr(path) for path, _ in flat]
    sparse = [i for i, mlp in enumerate(cfg.mlp_layer_types) if mlp == lg.SPARSE]

    def is_routed(name):
        return any(f"['layers'][{i}]" in name for i in sparse) and name.endswith(
            ROUTED_LEAVES
        )

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
        jax.value_and_grad(lambda p: ref.loss(p, tokens, ref_config, remat=True))
    )(params32)
    want_loss, want = float(want_loss), to_host(want)
    say(f"reference: loss {want_loss:.6f}", began)

    def reading(name, run_config, run_params, highest=False):
        began = time.monotonic()

        def loss_and_grads(p, t):
            with precision(highest):
                return jax.value_and_grad(lg.loss_fn)(p, t, run_config)

        loss, grads = jax.jit(loss_and_grads)(run_params, tokens)
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
        """(token, held expert) pairs routed otherwise than by the
        reference, a list with one count a sparse layer, and the pairs
        the reference routes there."""
        eps = cfg.rms_norm_eps

        def count(run_params, params32):
            x = run_params["embed"][tokens]
            with jax.default_matmul_precision("highest"):
                x32 = params32["embed"][tokens]
            differing, routed = [], []
            for i, (kind, mlp, blk, blk32) in enumerate(
                zip(cfg.layer_types, cfg.mlp_layer_types,
                    run_params["layers"], params32["layers"])
            ):
                if mlp == lg.SPARSE:
                    mid = x + lg.attention(
                        lg.rms_norm(x, blk["attn_norm"], eps), blk, kind, run_config
                    )
                    h = lg.rms_norm(mid, blk["mlp_norm"], eps)
                    _, mine = experts.held_gates(
                        h.reshape(-1, h.shape[-1]), blk["router"], None, run_config.routing
                    )
                    with jax.default_matmul_precision("highest"):
                        mid32 = x32 + ref.attention(
                            ref._rms_norm(x32, blk32["attn_norm"], eps), blk32, kind,
                            cfg.num_attention_heads_per_layer[i], ref_config,
                        )
                        h32 = ref._rms_norm(mid32, blk32["mlp_norm"], eps)
                        gates = ref.expert_gates(
                            h32.reshape(-1, h32.shape[-1]), blk32, ref_config
                        )
                    theirs = gates[:, jnp.asarray(cfg.expert_ids)] > 0
                    differing.append(jnp.sum(mine != theirs))
                    routed.append(jnp.sum(theirs))
                x = lg.layer(x, blk, kind, mlp, run_config)
                with jax.default_matmul_precision("highest"):
                    x32 = ref.layer(x32, blk32, i, ref_config)
            return jnp.stack(differing), jnp.stack(routed)

        def run(run_params, params32):
            with precision(highest):
                return count(run_params, params32)

        differing, routed = jax.jit(run)(run_params, params32)
        return [int(v) for v in differing], [int(v) for v in routed]

    f32_config = dataclasses.replace(cfg, dtype=jnp.float32)
    readings = {"float32": reading("float32", f32_config, params32, highest=True)}
    routing = {"float32": routed_otherwise(f32_config, params32, highest=True)}
    readings["bfloat16"] = reading("bfloat16", cfg, params)
    routing["bfloat16"] = routed_otherwise(cfg, params)
    del params32
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype) if p.ndim >= 2 else p,
        params,
    )
    readings["float8_weights"] = reading("float8_weights", cfg, rounded)
    del rounded

    def within(rows, loss_limit, leaf_limit):
        return rows["loss"]["relative"] <= loss_limit and all(
            leaf_limit(name, rows[name]) for name in names
        )

    by_limits = lambda name, r: r["cosine"] >= GRAD_COSINE and r["relative_l2"] <= GRAD_REL_L2
    verdicts = {
        "float32": within(
            readings["float32"], LOSS_REL,
            lambda name, r: r["relative_l2"]
            <= (F32_REL_L2_EXPERTS if is_routed(name) else F32_REL_L2),
        ),
        "bfloat16": within(readings["bfloat16"], LOSS_REL, by_limits),
        "float8_weights_fails": not within(readings["float8_weights"], LOSS_REL, by_limits),
    }
    ok = all(verdicts.values())
    summary = {
        name: {
            "loss_relative": rows["loss"]["relative"],
            "worst_cosine": min(rows[n]["cosine"] for n in names),
            "worst_relative_l2": max(rows[n]["relative_l2"] for n in names),
            "leaves_outside_the_limits": sum(not by_limits(n, rows[n]) for n in names),
        }
        for name, rows in readings.items()
    }
    device = jax.devices()[0]
    doc = {
        "ok": bool(ok), "seed": args.seed, "verdicts": verdicts, "leaves": len(names),
        "limits": {"loss_relative": LOSS_REL, "cosine": GRAD_COSINE,
                   "relative_l2": GRAD_REL_L2, "float32_relative_l2": F32_REL_L2,
                   "float32_relative_l2_experts": F32_REL_L2_EXPERTS},
        "summary": summary,
        "routed_otherwise": {
            name: {"differing_a_layer": d, "routed_a_layer": r}
            for name, (d, r) in routing.items()
        },
        "device": {"platform": device.platform, "kind": device.device_kind},
    }
    out = os.path.join(_CHECKOUT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"laguna_step_check-{args.seed}.json"), "w") as f:
        json.dump(dict(doc, readings=readings), f, indent=1)
    print(json.dumps(doc), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
