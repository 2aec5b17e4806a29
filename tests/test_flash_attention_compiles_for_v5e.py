"""The attention kernels at the widths the Laguna cell runs them,
compiled for a described (not attached) TPU v5e: what the Pallas
interpreter cannot show (a slice off the tiling, more VMEM than a kernel
may ask for). Forward and both backward kernels, 8192 positions in
1024-row tiles, head 128, grouped-query: the sliding layers' 64 heads
over 8 under a window of 512, the full layers' 48 over 8 plain causal;
and as the SDAR cell runs them: 32 heads over 4 under the block-diffusion
mask of two halves of 4096 in blocks of 4; and as the LFM2 cell runs
them: 32 heads over 8 at a head width of 64, plain causal.

Nothing runs: a compile that passes is no chip run. Skipped where no
such topology can be described. The topology is described inside a
fixture, in this one file (one process loads the TPU's library)."""

import pytest

import jax
import jax.numpy as jnp

from torchsnapshot_tpu.ops.attention import (
    _flash_backward,
    _flash_forward,
    resolve_flash_block,
)

SEQ, HEAD = 8192, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the runtime raises where it cannot
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "heads,kv_heads,mask,head",
    [
        (64, 8, {"window": 512}, HEAD),
        (48, 8, {}, HEAD),
        (32, 4, {"diffusion": (SEQ // 2, 4)}, HEAD),
        (32, 4, {"diffusion": (SEQ // 2, 3)}, HEAD),  # a block that is no shift
        (32, 8, {}, 64),
    ],
    ids=["sliding_64_heads", "full_48_heads", "block_diffusion_32_heads",
         "block_diffusion_block_of_3", "full_32_heads_of_64"],
)
def test_forward_and_backward_kernels_compile_at_the_cell_s_widths(
    one_chip, heads, kv_heads, mask, head
):
    block = resolve_flash_block(SEQ)
    assert block == 1024
    spec = lambda h, d=head, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        (1, h, SEQ, d), dtype, sharding=one_chip
    )
    q, kv = spec(heads), spec(kv_heads)
    forward = _flash_forward.lower(
        q, kv, kv, causal=True, block_q=block, block_k=block, interpret=False,
        **mask,
    ).compile()
    assert "tpu_custom_call" in forward.as_text()
    backward = _flash_backward.lower(
        q, kv, kv, q, spec(heads, 1, jnp.float32), spec(heads, 1, jnp.float32),
        causal=True, block_q=block, block_k=block, interpret=False, **mask,
    ).compile()
    assert backward.as_text().count("tpu_custom_call") >= 2
