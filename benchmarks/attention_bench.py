"""On-device attention kernel benchmark: fused (flash) Pallas vs dense
einsum, forward and forward+backward, across sequence lengths.

Unlike the snapshot benchmark (bounded by the host↔device link), this
measures ON-DEVICE compute: the timed region is a jitted `lax.fori_loop`
of attention steps, so dispatch/transfer overhead is amortized and the
number reflects kernel quality (MXU utilization, HBM traffic).

Run on a machine with a TPU (the kernels are compiled, never
interpreted: ``interpret=False`` is passed explicitly, so a CPU backend
fails instead of timing the interpreter):
    python benchmarks/attention_bench.py

Prints a table of per-step latency and achieved attention TFLOP/s
(4·B·H·S²·D FLOPs per forward — two matmuls, halved again when causal
— and 2.5× that for forward+backward).
"""

import sys
import time

import jax
import jax.numpy as jnp

import os  # noqa: E402

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

from torchsnapshot_tpu.ops.attention import (  # noqa: E402
    _reference_attention,
    flash_attention,
    resolve_flash_block,
)

ITERS = 300


def _bench(fn, *args) -> float:
    """Median per-call seconds of a jitted loop of ITERS calls.

    The output feeds the next iteration's first argument (same shape),
    so the body has a true loop-carried dependency — XLA can neither
    hoist the attention out of the loop nor dead-code it (a
    multiply-by-zero feedback gets constant-folded and the 'benchmark'
    then measures one call amortized over ITERS)."""

    @jax.jit
    def loop(args):
        def body(_, carry):
            q = carry[0]
            out = fn(*carry)
            return (out.astype(q.dtype),) + carry[1:]

        return jnp.sum(
            jax.lax.fori_loop(0, ITERS, body, args)[0].astype(jnp.float32)
        )

    float(loop(args))  # compile
    times = []
    for _ in range(3):
        begin = time.monotonic()
        # float() fetches the scalar VALUE: the timed region ends when
        # the device work has finished, not when it was dispatched.
        float(loop(args))
        times.append((time.monotonic() - begin) / ITERS)
    return sorted(times)[1]


def main() -> None:
    from torchsnapshot_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache(_REPO_ROOT)
    device = jax.devices()[0]
    print(f"platform={device.platform} device_kind={device.device_kind}")
    b, h, d = 2, 16, 128
    print(f"B={b} H={h} D={d}, bf16, causal; {ITERS}-step jitted loop (latency amortized)")
    print(
        f"{'S':>6} {'flash fwd':>11} {'einsum fwd':>11} {'speedup':>8} "
        f"{'flash TFLOP/s':>13}  {'fwd+bwd flash':>13}"
    )
    for s in (1024, 2048, 4096, 8192):
        kq, kk, kv = jax.random.split(jax.random.key(s), 3)
        q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
        v = jax.random.normal(kv, (b, h, s, d), jnp.bfloat16)
        block = resolve_flash_block(s)

        def flash_fn(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q=block, block_k=block,
                interpret=False,
            )

        def einsum_fn(q, k, v):
            return _reference_attention(q, k, v, True)

        t_flash = _bench(flash_fn, q, k, v)
        t_einsum = _bench(einsum_fn, q, k, v) if s <= 4096 else float("nan")

        def flash_grad(q, k, v):
            # argnums MUST cover k and v: with argnums=0 the dk/dv
            # Pallas kernel is dead code under jit and XLA DCEs it —
            # the "fwd+bwd" number would then time only fwd + dq
            # (~half the backward FLOPs missing).
            return jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_fn(q, k, v).astype(jnp.float32) ** 2
                ),
                argnums=(0, 1, 2),
            )(q, k, v)

        @jax.jit
        def bwd_loop(q, k, v):
            def body(_, carry):
                dq, dk, dv = flash_grad(*carry)
                # All three grads feed the next iteration, so none of
                # the backward kernels can be dead-code-eliminated.
                return (
                    dq.astype(q.dtype),
                    dk.astype(k.dtype),
                    dv.astype(v.dtype),
                )

            out = jax.lax.fori_loop(0, ITERS, body, (q, k, v))
            return sum(jnp.sum(x.astype(jnp.float32)) for x in out)

        float(bwd_loop(q, k, v))  # compile
        bwd_times = []
        for _ in range(3):
            begin = time.monotonic()
            float(bwd_loop(q, k, v))
            bwd_times.append((time.monotonic() - begin) / ITERS)
        t_bwd = sorted(bwd_times)[1]

        causal_flops = 4 * b * h * s * s * d / 2
        tflops = causal_flops / t_flash / 1e12
        print(
            f"{s:>6} {t_flash * 1e3:>9.2f}ms {t_einsum * 1e3:>9.2f}ms "
            f"{t_einsum / t_flash:>7.2f}x {tflops:>13.2f} "
            f"{t_bwd * 1e3:>11.2f}ms"
        )


if __name__ == "__main__":
    main()
