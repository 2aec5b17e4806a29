"""One step of the ``nemotron_h_adam`` job at the published widths against
the plain reference, on the chip: the loss, and the gradient of one leaf
of each block kind (and of the head).

    chiprun -- python3 tools/check_nemotron_h_step.py [--seed N]

The reference (``perfbench/reference_nemotron_h.py``) computes the
program's weights in float32 under ``jax.default_matmul_precision(
"highest")``, block by block with each block recomputed in the backward
pass, the time scan in recomputed segments and the attention scores a
block of queries at a time, so that it fits. Against it, three readings
of the program (``models/nemotron_h.py``), and the script exits 0 only
if all three come out as they have to:

- ``float32``: the program computing in float32 under "highest". The
  same mathematics in another order (chunked scan, gathered experts,
  flash attention), so this one is tight: it shows that what runs on
  the chip is the reference's model.
- ``bfloat16``: the program as the configuration states it. Within the
  limits below.
- ``float8_weights``: the bfloat16 program on weights rounded to
  float8_e4m3 (the nearest precision below): has to FAIL the limits,
  by at least one of them, or the limits could not tell precisions apart.

It also counts, in every expert block, for how many (token, held
expert) pairs the program routes otherwise than the reference: at
random weights a gradient is a sum of near-orthogonal per-token terms,
so an expert whose token set changes by a share f moves its gradient by
about sqrt(2 f), which is most of what the expert leaves read.

Limits. First set before any run (loss 2e-2 relative; cosine >= 0.99,
difference's norm <= 0.15 of the reference's): the first run (seed
2147484700, my chip run, PR 28) read loss 6.3e-5; in_proj 0.9991 /
0.042; A_log 0.9990 / 0.052; experts' up 0.9943 / 0.107; router 0.9886 /
0.152; wq 0.9986 / 0.052; head 0.9995 / 0.031, so the router missed both
by a hair, and the loss limit was 300 times the reading. The cause is
not in the program: computing in float32 it reads 7e-6 to 2.4e-4 on
every leaf with not one pair routed otherwise, while in bfloat16 26, 52,
77 and 90 of about 2500 to 3300 routed pairs a block differ (same
seed), and sqrt(2 * 26 / 3296) = 0.126 is what the first expert block's
leaves read. The limits are now set between two readings, as a limit
has to be, with room on both sides: the bfloat16 program (worst leaf
cosine 0.9886, norm 0.152; loss 6.3e-5) and the float8-weights control
(best leaf cosine 0.878, norm 0.49; loss 5.5e-4), `PERF.md` §6, PR 28:

- loss: 3e-4 relative.
- gradients: cosine >= 0.97 and difference's norm <= 0.25 of the
  reference's on every leaf compared.
- float32 reading: difference's norm <= 2e-3 on every leaf but the
  expert block's two (<= 0.05: one token of 8192 routed otherwise, at a
  tie that float32 reassociation decides, would move an expert's
  gradient by sqrt(2 / 384) = 7 % of its own).
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
GRAD_COSINE = 0.97
GRAD_REL_L2 = 0.25
F32_REL_L2 = 2e-3
F32_REL_L2_EXPERTS = 0.05
CELL = "nemotron3-nano-30b-a3b-ep16.save_in_loop"


def leaves_compared(pattern):
    """One block of each kind (its first), and the head."""
    m, e, a = (pattern.index(kind) for kind in "ME*")
    return {
        "M": ("blocks", m, "in_proj"),
        "M.A_log": ("blocks", m, "A_log"),
        "E": ("blocks", e, "up"),
        "E.router": ("blocks", e, "router"),
        "*": ("blocks", a, "wq"),
        "head": ("head",),
    }


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=2147484700)
    parser.add_argument(
        "--config", help="a configuration file of the job, for a rehearsal "
        "at a toy size on the CPU (default: the cell's own)",
    )
    args = parser.parse_args(argv)

    from perfbench import manifest
    from perfbench import reference_nemotron_h as ref
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_CHECKOUT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchsnapshot_tpu.models import nemotron_h as nh

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    config = dict(cell.config)
    if args.config:
        with open(args.config) as f:
            config = json.load(f)
    job = manifest.load_module(cell.job_path).make_job(
        config, jax.devices()[:1], args.seed
    )
    cfg = job.cfg
    leaves = leaves_compared(cfg.pattern)
    params = jax.jit(
        lambda key: jax.tree.map(
            lambda w: w.astype(cfg.dtype), nh.init_master(cfg, key)
        )
    )(jax.random.key(args.seed & 0x7FFFFFFF))
    tokens = job.tokens(0)
    ref_config = dict(config, pattern=cfg.pattern, n_routed_experts=cfg.n_routed_experts)

    def precision(highest):
        if highest:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()

    def say(what, began):
        print(f"{what} in {time.monotonic() - began:.1f} s", flush=True)

    # ---- the reference: loss, and the gradients of the leaves compared
    def ref_loss(wanted, params):
        # The wanted leaves enter as float32 arguments of their own, so
        # that the reference differentiates with respect to them alone.
        params = jax.tree.map(lambda p: p, params)
        for name, path in leaves.items():
            _at(params, path[:-1])[path[-1]] = wanted[name]
        return ref.loss(params, tokens, ref_config, remat=True)

    began = time.monotonic()
    wanted = {n: _at(params, p).astype(jnp.float32) for n, p in leaves.items()}
    want_loss, want = jax.jit(jax.value_and_grad(ref_loss))(wanted, params)
    want_loss = float(want_loss)
    want = {n: np.asarray(g, np.float64).ravel() for n, g in want.items()}
    say(f"reference: loss {want_loss:.6f}", began)

    def reading(name, run_config, run_params, highest=False):
        began = time.monotonic()

        def loss_and_grads(p, t):
            with precision(highest):
                return jax.value_and_grad(nh.loss_fn)(p, t, run_config)

        loss, grads = jax.jit(loss_and_grads)(run_params, tokens)
        loss = float(loss)
        rows = {"loss": {"program": loss, "reference": want_loss,
                         "relative": abs(loss - want_loss) / abs(want_loss)}}
        for leaf, path in leaves.items():
            g = np.asarray(_at(grads, path), np.float64).ravel()
            w = want[leaf]
            rows[leaf] = {
                "cosine": float(g @ w / (np.linalg.norm(g) * np.linalg.norm(w))),
                "relative_l2": float(np.linalg.norm(g - w) / np.linalg.norm(w)),
            }
        say(f"{name}: loss {loss:.6f}", began)
        return rows

    def routed_otherwise(run_config, run_params, highest=False):
        """(token, held expert) pairs routed otherwise than by the
        reference, a list with one count an expert block, and the pairs
        the reference routes there."""
        def count(run_params, params32):
            x = run_params["embed"][tokens]
            with jax.default_matmul_precision("highest"):
                x32 = params32["embed"][tokens]
            differing, routed = [], []
            for kind, blk, blk32 in zip(
                cfg.pattern, run_params["blocks"], params32["blocks"]
            ):
                if kind == "E":
                    normed = nh.rms_norm(x, blk["norm"], cfg.norm_eps)
                    _, mine = nh.held_gates(normed.reshape(-1, x.shape[-1]), blk, run_config)
                    with jax.default_matmul_precision("highest"):
                        normed32 = ref._rms_norm(x32, blk32["norm"], ref_config["norm_eps"])
                        gates = ref.expert_gates(
                            normed32.reshape(-1, x.shape[-1]), blk32, ref_config
                        )
                    theirs = gates[:, jnp.asarray(cfg.expert_ids)] > 0
                    differing.append(jnp.sum(mine != theirs))
                    routed.append(jnp.sum(theirs))
                x = nh.block(x, blk, kind, run_config)
                with jax.default_matmul_precision("highest"):
                    x32 = ref.block(x32, blk32, kind, ref_config)
            return jnp.stack(differing), jnp.stack(routed)

        def run(run_params, params32):
            with precision(highest):
                return count(run_params, params32)

        params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        differing, routed = jax.jit(run)(run_params, params32)
        return [int(v) for v in differing], [int(v) for v in routed]

    f32_config = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    readings = {"float32": reading("float32", f32_config, params32, highest=True)}
    routing = {"float32": routed_otherwise(f32_config, params32, highest=True)}
    del params32
    readings["bfloat16"] = reading("bfloat16", cfg, params)
    routing["bfloat16"] = routed_otherwise(cfg, params)
    rounded = jax.tree.map(
        lambda p: p.astype(jnp.float8_e4m3fn).astype(p.dtype) if p.ndim >= 2 else p,
        params,
    )
    readings["float8_weights"] = reading("float8_weights", cfg, rounded)
    del rounded

    def within(rows, loss_limit, leaf_limit):
        return rows["loss"]["relative"] <= loss_limit and all(
            leaf_limit(name, rows[name]) for name in leaves
        )

    by_limits = lambda name, r: r["cosine"] >= GRAD_COSINE and r["relative_l2"] <= GRAD_REL_L2
    verdicts = {
        "float32": within(
            readings["float32"], LOSS_REL,
            lambda name, r: r["relative_l2"]
            <= (F32_REL_L2_EXPERTS if name.startswith("E") else F32_REL_L2),
        ),
        "bfloat16": within(readings["bfloat16"], LOSS_REL, by_limits),
        "float8_weights_fails": not within(readings["float8_weights"], LOSS_REL, by_limits),
    }
    ok = all(verdicts.values())
    device = jax.devices()[0]
    doc = {
        "ok": bool(ok), "seed": args.seed, "verdicts": verdicts,
        "limits": {"loss_relative": LOSS_REL, "cosine": GRAD_COSINE,
                   "relative_l2": GRAD_REL_L2, "float32_relative_l2": F32_REL_L2,
                   "float32_relative_l2_experts": F32_REL_L2_EXPERTS},
        "readings": readings,
        "routed_otherwise": {
            name: {"differing_a_block": d, "routed_a_block": r}
            for name, (d, r) in routing.items()
        },
        "device": {"platform": device.platform, "kind": device.device_kind},
    }
    out = os.path.join(_CHECKOUT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"nemotron_h_step_check-{args.seed}.json"), "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
