"""Compile the main path's Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described, not attached (`jax.experimental.topologies`).  Interpret mode,
which every other test uses, cannot see what Mosaic refuses: a block
shape off the (8, 128) tiling, a primitive it does not lower (`cumsum`,
a dynamic slice of a value), or a kernel that overflows scoped VMEM.
These compiles catch those here.  Nothing runs, so they say nothing about
results or times.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash import flash_causal_attention
from repro.kernels.frontier import (
    TierAxes,
    co_activation,
    fused_fleet_tick,
    tiered_co_activation,
)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize(
    "jnrs", [(64, 20, 128, 6), (16, 20, 8, 6), (4, 50, 1024, 6)]
)
def test_fused_tick_service_configuration(one_chip, jnrs):
    # what `FleetService.refresh_batched` dispatches: cohort medians, no
    # regime or co-activation family
    d = jax.ShapeDtypeStruct(jnrs, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda x: fused_fleet_tick(
            x, sync_stages=(2,), with_regimes=False, interpret=False
        ),
        d,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [20, 100])
def test_fused_tick_every_family_fits_vmem(one_chip, n):
    # the widest kernel: all four families at R=1024.  A window of 100
    # steps needs the narrower rank tile the wrapper derives from N (at
    # R_TILE=512 it overflows scoped VMEM).  An explicit baseline keeps
    # the median's XLA sort out of this compile.
    d = jax.ShapeDtypeStruct((4, n, 1024, 6), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((6,), jnp.float32, sharding=one_chip)
    hosts = jax.ShapeDtypeStruct((4, 1024), jnp.int32, sharding=one_chip)
    text = _compiled_text(
        lambda x, base, h: fused_fleet_tick(
            x, base, sync_stages=(2,), host_index=h, num_hosts=128,
            interpret=False,
        ),
        d, b, hosts,
    )
    assert "tpu_custom_call" in text


def test_co_activation(one_chip):
    act = jax.ShapeDtypeStruct((64, 20, 300, 6), jnp.bool_, sharding=one_chip)
    text = _compiled_text(lambda a: co_activation(a, interpret=False), act)
    assert "tpu_custom_call" in text


def test_tiered_co_activation(one_chip):
    act = jax.ShapeDtypeStruct((64, 20, 300, 6), jnp.bool_, sharding=one_chip)
    tiers = [
        TierAxes("switch", 10, tuple(h // 30 for h in range(300))),
        TierAxes("pod", 2, tuple(h // 150 for h in range(300))),
    ]
    text = _compiled_text(
        lambda a: tiered_co_activation(a, tiers, interpret=False), act
    )
    assert "tpu_custom_call" in text


def test_flash_attention_forward_and_backward(one_chip):
    # paper-gpt-125m's attention at its cell's shape (B 8, S 1024, H 12,
    # D 64) with the committed tile sizes, under `jax.grad`: the forward
    # with residuals and both backward kernels (dkv, dq)
    qkv = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return flash_causal_attention(q, k, v).astype(jnp.float32).sum()

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3
