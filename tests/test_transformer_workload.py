"""Flagship workload integration: snapshot/restore a sharded transformer
train state (params + optax Adam moments) across mesh shapes.

The TPU-scale analog of BASELINE.json's "FSDP Llama sharded snapshot →
elastic restore onto a different pod shape" config, scaled down to the
8-device virtual CPU mesh: train a few steps, snapshot (sync and
device-staged async), then restore onto a differently-shaped mesh and
continue training — losses must match bit-exactly.

Marked ``slow``: the flagship model's attention runs the Pallas kernel
in interpreter mode on the hermetic CPU suite, so each train step costs
minutes of trace time on a single-core host. The snapshot machinery the
file integrates is covered in the fast tier by test_snapshot /
test_elastic / test_roundtrip_fuzz. Run with ``-m slow`` (or no ``-m``
filter)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

pytestmark = pytest.mark.slow

from torchsnapshot_tpu import Snapshot
from torchsnapshot_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    loss_fn,
    shard_params,
)
from torchsnapshot_tpu.utils.test_utils import assert_state_dict_eq
from torchsnapshot_tpu.utils.train_state import PytreeStateful
from torchsnapshot_tpu.utils.tree import to_state_dict

CONFIG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq_len=16
)


def _make_state(mesh):
    params = init_params(CONFIG, jax.random.key(0))
    params = shard_params(params, mesh)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    return params, opt, opt_state


def _steps(params, opt, opt_state, mesh, n, seed=1):
    losses = []
    for i in range(n):
        tokens = jax.random.randint(
            jax.random.key(seed + i), (4, 16), 0, CONFIG.vocab_size
        )
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, tokens, CONFIG, mesh)
        )(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    return params, opt_state, losses


@pytest.mark.parametrize("take_mode", ["sync", "async"])
def test_transformer_elastic_resume(tmp_path, take_mode):
    devices = np.array(jax.devices()).reshape(4, 2)
    mesh = Mesh(devices, ("dp", "tp"))
    params, opt, opt_state = _make_state(mesh)
    params, opt_state, _ = _steps(params, opt, opt_state, mesh, 2)

    app = {
        "params": PytreeStateful(params),
        "opt": PytreeStateful(opt_state, convert=True),
    }
    path = str(tmp_path / "snap")
    if take_mode == "sync":
        Snapshot.take(path, app)
    else:
        pending = Snapshot.async_take(path, app, stage="device")
        pending.wait()

    # Ground truth: continue on the original mesh.
    _, _, expected_losses = _steps(params, opt, opt_state, mesh, 2, seed=9)

    # Elastic restore: different mesh shape AND fewer devices.
    mesh2 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    params2 = jax.tree.map(
        lambda a: jax.device_put(jnp.zeros_like(a), _resharded(a, mesh2)),
        params,
    )
    opt_state2 = jax.tree.map(
        lambda a: (
            jax.device_put(jnp.zeros_like(a), _resharded(a, mesh2))
            if isinstance(a, jax.Array)
            else a
        ),
        opt.init(params2),
    )
    target = {
        "params": PytreeStateful(params2),
        "opt": PytreeStateful(opt_state2, convert=True),
    }
    Snapshot(path).restore(target)
    params2, opt_state2 = target["params"].tree, target["opt"].tree

    # Bit-exact state, structure-checked (params and Adam moments).
    assert_state_dict_eq(to_state_dict(params), to_state_dict(params2))
    assert_state_dict_eq(to_state_dict(opt_state), to_state_dict(opt_state2))

    # Continued training on the new mesh: reduction order differs across
    # mesh shapes, so losses match to tight tolerance rather than bitwise.
    _, _, resumed_losses = _steps(params2, opt, opt_state2, mesh2, 2, seed=9)
    np.testing.assert_allclose(resumed_losses, expected_losses, rtol=1e-6)

    # Bit-exact resume guarantee holds on the *same* mesh: restore onto an
    # identically-sharded template and the continued losses are identical.
    params_same = jax.tree.map(
        lambda a: jax.device_put(jnp.zeros_like(a), a.sharding), params
    )
    opt_state_same = jax.tree.map(
        lambda a: (
            jax.device_put(jnp.zeros_like(a), _resharded(a, mesh))
            if isinstance(a, jax.Array)
            else a
        ),
        opt_state,
    )
    target_same = {
        "params": PytreeStateful(params_same),
        "opt": PytreeStateful(opt_state_same, convert=True),
    }
    Snapshot(path).restore(target_same)
    assert_state_dict_eq(
        to_state_dict(opt_state), to_state_dict(target_same["opt"].tree)
    )
    _, _, same_mesh_losses = _steps(
        target_same["params"].tree, opt, target_same["opt"].tree, mesh, 2, seed=9
    )
    assert same_mesh_losses == expected_losses


def _resharded(arr, new_mesh):
    """Map an array's NamedSharding spec onto a new mesh."""
    sharding = arr.sharding
    if isinstance(sharding, NamedSharding):
        return NamedSharding(new_mesh, sharding.spec)
    return NamedSharding(new_mesh, P())


def test_gqa_transformer_all_attention_paths_agree():
    """n_kv_heads < n_heads: the dense einsum (repeat-kv reference) and
    the flash kernel (index-map GQA) produce the same loss, the einsum
    loss is the same under a dp x sp x tp mesh, and the GQA train step
    runs jitted on that mesh with kv heads sharded over tp."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torchsnapshot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        loss_fn,
        sgd_train_step,
        shard_params,
    )

    kw = dict(
        vocab_size=64, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2,
        d_ff=64, max_seq_len=32,
    )
    dense = TransformerConfig(**kw)
    params = init_params(dense, jax.random.key(0))
    # wk/wv are [d_model, n_kv*head_dim] — the GQA shape.
    assert params["layers"][0]["attn"]["wk"].shape == (64, 16)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)

    loss_dense = float(loss_fn(params, tokens, dense))
    flash = TransformerConfig(**kw, flash_attention=True)
    loss_flash = float(loss_fn(params, tokens, flash))
    np.testing.assert_allclose(loss_flash, loss_dense, rtol=1e-5)

    devices = np.array(jax.devices()).reshape(2, 2, 2)
    mesh = Mesh(devices, ("dp", "sp", "tp"))
    sharded = shard_params(params, mesh)
    tok_sharded = jax.device_put(
        tokens.repeat(2, axis=0), NamedSharding(mesh, P("dp", "sp"))
    )
    loss_dense_sharded = float(
        jax.jit(lambda p, t: loss_fn(p, t, dense, mesh))(sharded, tok_sharded)
    )
    np.testing.assert_allclose(loss_dense_sharded, loss_dense, rtol=1e-5)

    _, loss = jax.jit(
        lambda p, t: sgd_train_step(p, t, config=dense, mesh=mesh)
    )(sharded, tok_sharded)
    assert np.isfinite(float(loss))


def test_gqa_rejects_indivisible_heads():
    from torchsnapshot_tpu.models.transformer import (
        TransformerConfig,
        init_params,
    )

    import jax
    import pytest

    cfg = TransformerConfig(n_heads=4, n_kv_heads=3)
    with pytest.raises(ValueError, match="multiple of"):
        init_params(cfg, jax.random.key(0))
