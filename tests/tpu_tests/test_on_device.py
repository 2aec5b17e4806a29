"""Real-accelerator integration tier (reference analog:
tests/gpu_tests/test_torchrec.py — skipped without the accelerator).

Run on a machine with a TPU:

    TPUSNAPSHOT_TPU_TESTS=1 python -m pytest tests/tpu_tests -q

That invocation FAILS when JAX finds no TPU (tests/conftest.py). Under
the default hermetic suite (``pytest tests/``) the platform is forced to
cpu and every test here self-skips. On a TPU backend the Pallas kernels
compile through Mosaic (``resolve_interpret()`` is False) — nothing here
runs interpreted.
"""

import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchsnapshot_tpu import Snapshot, StateDict
from torchsnapshot_tpu.utils.train_state import PytreeStateful

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="real-accelerator tier; run with TPUSNAPSHOT_TPU_TESTS=1 "
    "python -m pytest tests/tpu_tests on a machine with a TPU",
)


def test_kernels_compile_not_interpret():
    from torchsnapshot_tpu.ops.attention import resolve_interpret

    assert resolve_interpret() is False


def test_device_array_round_trip_bitexact(tmp_path):
    """HBM → storage → HBM round-trip of a ~64 MB array, chunked-transfer
    path included, compared byte-for-byte."""
    key = jax.random.key(0)
    arr = jax.random.normal(key, (16, 1024, 1024), jnp.float32)
    arr.block_until_ready()
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"s": StateDict(w=arr)})
    target = StateDict(w=jnp.zeros_like(arr))
    Snapshot(path).restore({"s": target})
    np.testing.assert_array_equal(np.asarray(target["w"]), np.asarray(arr))
    assert next(iter(target["w"].devices())).platform != "cpu"


def test_bf16_on_device_bitexact(tmp_path):
    arr = jax.random.normal(jax.random.key(1), (333, 517), jnp.bfloat16)
    arr.block_until_ready()
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"s": StateDict(w=arr)})
    target = StateDict(w=jnp.zeros_like(arr))
    Snapshot(path).restore({"s": target})
    np.testing.assert_array_equal(
        np.asarray(target["w"]).view(np.uint16),
        np.asarray(arr).view(np.uint16),
    )


def test_async_take_device_stage(tmp_path):
    """Device-staged consistent cut on real HBM: mutate (rebind) the
    source immediately after async_take returns; the snapshot must hold
    the pre-mutation values."""
    state = {"w": jnp.ones((8, 1024, 1024), jnp.float32)}
    holder = PytreeStateful(state)
    pending = Snapshot.async_take(
        str(tmp_path / "snap"), {"m": holder}, stage="device"
    )
    holder.tree = {"w": state["w"] * -1}
    snap = pending.wait()
    target = PytreeStateful({"w": jnp.zeros((8, 1024, 1024), jnp.float32)})
    snap.restore({"m": target})
    assert float(np.asarray(target.tree["w"]).min()) == 1.0


def test_flash_attention_kernel_on_device():
    """The fused attention Pallas kernel compiles via Mosaic and matches
    the einsum reference on real hardware (bf16 inputs)."""
    from torchsnapshot_tpu.ops.attention import (
        _reference_attention,
        flash_attention,
    )

    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    shape = (2, 4, 512, 64)
    q = jax.random.normal(kq, shape, jnp.bfloat16)
    k = jax.random.normal(kk, shape, jnp.bfloat16)
    v = jax.random.normal(kv, shape, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    expected = _reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), True
    )
    err = float(jnp.abs(out.astype(jnp.float32) - expected).max())
    assert err < 2e-2, err


@pytest.mark.parametrize(
    "precision,tol", [("default", 5e-2), ("highest", 1e-3)]
)
def test_flash_model_tiles_on_device(precision, tol):
    """The tiles the model path picks at seq 2048 (1024 rows, head dim
    128, float32): forward and all three gradients compile under Mosaic
    and match the einsum reference — at the default matmul precision and
    at ``highest``, where the backward needs the explicit VMEM request
    (``_compiler_params``) or Mosaic refuses it."""
    from torchsnapshot_tpu.ops.attention import (
        _reference_attention,
        flash_attention,
        resolve_flash_block,
    )

    s = 2048
    block = resolve_flash_block(s)
    assert block == 1024
    kq, kk, kv = jax.random.split(jax.random.key(13), 3)
    shape = (1, 4, s, 128)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, block_q=block, block_k=block
    )
    ref = lambda q, k, v: _reference_attention(q, k, v, True)  # noqa: E731
    with jax.default_matmul_precision(precision):
        np.testing.assert_allclose(
            np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=tol
        )
        gf = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=tol, rtol=tol
        )


@pytest.mark.parametrize(
    "dtype,s", [(jnp.float32, 8), (jnp.bfloat16, 8), (jnp.bfloat16, 16)]
)
def test_flash_minimum_blocks_on_device(dtype, s):
    """The smallest blocks the tiling policy accepts: 8 rows is one
    float32 sublane tile but half a bfloat16 one."""
    from torchsnapshot_tpu.ops.attention import (
        _reference_attention,
        flash_attention,
        resolve_flash_block,
    )

    block = resolve_flash_block(s)
    kq, kk, kv = jax.random.split(jax.random.key(17), 3)
    shape = (1, 2, s, 128)
    q = jax.random.normal(kq, shape, dtype)
    k = jax.random.normal(kk, shape, dtype)
    v = jax.random.normal(kv, shape, dtype)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block)
    expected = _reference_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), True
    )
    err = float(jnp.abs(out.astype(jnp.float32) - expected).max())
    assert err < 3e-2, err
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for g in grads:
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_flash_long_context_on_device():
    """32k-token causal attention on one chip: the fused kernel's O(S·D)
    memory is what makes this run at all — the dense path's score matrix
    would need B·H·S² f32 = 34 GB of HBM."""
    from torchsnapshot_tpu.ops.attention import flash_attention

    S = 32768
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(kq, (1, 8, S, 64), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 8, S, 64), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 8, S, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    out.block_until_ready()
    assert out.shape == (1, 8, S, 64)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_flash_long_context_gradients_on_device():
    """Training-path long context: grads at 16k tokens on one chip. The
    tiled Pallas backward reconstructs p per tile from the saved
    log-sum-exp — a dense backward would materialize B·H·S² probability
    + score tensors (~17 GB here)."""
    from torchsnapshot_tpu.ops.attention import flash_attention

    S = 16384
    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (1, 8, S, 64), jnp.bfloat16)
    k = jax.random.normal(kk, (1, 8, S, 64), jnp.bfloat16)
    v = jax.random.normal(kv, (1, 8, S, 64), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    jax.block_until_ready(grads)
    for g in grads:
        assert g.shape == (1, 8, S, 64)
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


def test_flash_gqa_on_device():
    """GQA index maps lower under Mosaic: 8 q heads sharing 2 kv heads,
    forward + gradients on real TPU vs the repeat-kv einsum reference."""
    from torchsnapshot_tpu.ops.attention import (
        _reference_attention,
        flash_attention,
    )

    b, hq, hkv, s, d = 1, 8, 2, 512, 64
    kq, kk, kv = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(kq, (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, hkv, s, d), jnp.bfloat16)

    out = flash_attention(q, k, v, causal=True)
    g = hq // hkv
    expected = _reference_attention(
        q.astype(jnp.float32),
        jnp.repeat(k, g, axis=1).astype(jnp.float32),
        jnp.repeat(v, g, axis=1).astype(jnp.float32),
        True,
    )
    err = float(jnp.abs(out.astype(jnp.float32) - expected).max())
    assert err < 2e-2, err

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2
        )

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    jax.block_until_ready(grads)
    assert grads[1].shape == (b, hkv, s, d)
    for gr in grads:
        assert bool(jnp.isfinite(gr.astype(jnp.float32)).all())


def test_streaming_restore_device_budget_on_device(tmp_path, monkeypatch):
    """HBM admission control on the real chip (SURVEY §7 hard-part 5):
    two arrays whose combined streamed chunks exceed a forced device
    budget restore bit-exactly — regions admitted one at a time against
    the budget, with the resident halves staying charged. The payload is
    small (~128 MiB); the budget forces the same contention a
    near-HBM-capacity restore hits at full scale."""
    import torchsnapshot_tpu.io_preparer as iop

    monkeypatch.setattr(iop, "MAX_CHUNK_SIZE_BYTES", 16 << 20)
    monkeypatch.setenv(
        "TPUSNAPSHOT_PARALLEL_READ_THRESHOLD", str(4 << 20)
    )
    # Each 64 MiB region charges 2x its size; 160 MiB admits one region
    # (128 MiB charge) but never both at once.
    monkeypatch.setenv(
        "TPUSNAPSHOT_DEVICE_BUDGET_BYTES", str(160 << 20)
    )
    a = jax.random.normal(jax.random.key(11), (16 << 20,), jnp.float32)
    b = jax.random.normal(jax.random.key(12), (16 << 20,), jnp.float32)
    jax.block_until_ready((a, b))
    path = str(tmp_path / "snap")
    Snapshot.take(path, {"s": StateDict(a=a, b=b)})
    target = StateDict(a=jnp.zeros_like(a), b=jnp.zeros_like(b))
    Snapshot(path).restore({"s": target})
    eq = jax.jit(lambda x, y: jnp.all(x == y))
    assert bool(eq(target["a"], a)) and bool(eq(target["b"], b))
    assert next(iter(target["a"].devices())).platform != "cpu"


def test_incremental_take_on_device(tmp_path):
    """Incremental dedup on the real chip: device fingerprints, skipped
    D2H for frozen leaves, device-verified restore (round 5)."""
    frozen = jax.random.normal(jax.random.key(7), (4, 1024, 1024), jnp.float32)
    head = jax.random.normal(jax.random.key(8), (1024,), jnp.float32)
    jax.block_until_ready((frozen, head))
    s1 = Snapshot.take(
        str(tmp_path / "s1"),
        {"m": StateDict(frozen=frozen, head=head)},
        fingerprint=True,
    )
    s2 = Snapshot.take(
        str(tmp_path / "s2"),
        {"m": StateDict(frozen=frozen, head=head + 1.0)},
        base=s1,
    )
    m = s2.get_manifest()
    frozen_entry = m["0/m/frozen"]
    refs = (
        [s.array for s in frozen_entry.shards]
        if hasattr(frozen_entry, "shards")
        else [frozen_entry]
    )
    assert all(a.base is not None for a in refs)
    assert m["0/m/head"].base is None
    target = StateDict(
        frozen=jnp.zeros_like(frozen), head=jnp.zeros_like(head)
    )
    s2.restore({"m": target}, verify_device=True)
    np.testing.assert_array_equal(np.asarray(target["frozen"]), np.asarray(frozen))
    np.testing.assert_array_equal(
        np.asarray(target["head"]), np.asarray(head) + 1.0
    )
    assert next(iter(target["frozen"].devices())).platform != "cpu"
    assert s2.verify() == {}
