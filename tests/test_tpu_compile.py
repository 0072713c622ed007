"""The main path's Pallas kernels compile for a TPU v5e chip.

The TPU compiler compiles for a chip that is described, not attached, so
these tests refuse here what the chip's compiler would refuse (block shapes
Mosaic cannot tile, more VMEM than a kernel may use) at stablelm-1.6b
widths. Interpret mode accepts all of that, so each compile passes
``interpret=False`` and checks that the kernel really is in the program
(``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the suite runs in several.
All such compiles live in this one file for the same reason. The tests skip
only where the TPU compiler is not installed; any other failure to describe
the chip fails them.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attn.paged import paged_decode_attn
from repro.kernels.wagg import wagg_fused

# one stablelm-1.6b FFN leaf, p=2 workers: (p, d_model * d_ff)
WAGG_P, WAGG_N = 2, 2048 * 5632


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: an entry written
    for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("payload_dtype,masked", [
    (None, False),                  # f32 codec: the payload is x itself
    (jnp.int8, True),               # int8 wire tiles + Alg. 4 mask
    (jnp.bfloat16, False),
])
def test_wagg_fused_compiles_for_v5e(one_chip, payload_dtype, masked):
    p, n = WAGG_P, WAGG_N
    args = [_shape(one_chip, (p, n), jnp.float32),
            _shape(one_chip, (p,), jnp.float32),
            None if payload_dtype is None
            else _shape(one_chip, (p, n), payload_dtype),
            _shape(one_chip, (p,), jnp.float32) if masked else None]

    def agg(x, theta, payload, active):
        return wagg_fused(x, theta, 0.9, payload=payload, active=active,
                          interpret=False)

    compiled = jax.jit(agg).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (b, kv, g, hd, bs, n_blk, ring, window)
PAGED_CASES = [
    # stablelm-1.6b decode: kv=32 heads of 64, 8 requests of up to 160
    # tokens; the ring cases are the layout windowed archs serve through
    pytest.param(8, 32, 1, 64, 16, 10, None, None, id="None-None"),
    pytest.param(8, 32, 1, 64, 16, 10, 160, None, id="160-None"),
    pytest.param(8, 32, 1, 64, 16, 10, 160, 100, id="160-100"),
    # Yi-6B serving: 16 slots, 4 kv heads of 128 under 8 query heads each,
    # a 4096-token table (256 blocks)
    pytest.param(16, 4, 8, 128, 16, 256, None, None, id="yi-None-None"),
    pytest.param(16, 4, 8, 128, 16, 256, 4096, 1024, id="yi-4096-1024"),
]


@pytest.mark.parametrize("b,kv,g,hd,bs,n_blk,ring,window", PAGED_CASES)
def test_paged_decode_attn_compiles_for_v5e(one_chip, b, kv, g, hd, bs, n_blk,
                                            ring, window):
    """Paged decode with bf16 pools of 16-token blocks, the per-row
    ``active`` mask given, at stablelm's and Yi's widths."""
    n_pool = b * n_blk + 1
    args = [_shape(one_chip, (b, kv, g, hd), jnp.bfloat16),
            _shape(one_chip, (n_pool, bs, kv, hd), jnp.bfloat16),
            _shape(one_chip, (n_pool, bs, kv, hd), jnp.bfloat16),
            _shape(one_chip, (b, n_blk), jnp.int32),
            _shape(one_chip, (b,), jnp.int32),
            _shape(one_chip, (b,), jnp.bool_)]

    def attn(q, k, v, table, index, active):
        return paged_decode_attn(q, k, v, table, index, active, ring=ring,
                                 window=window, interpret=False)

    compiled = jax.jit(attn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
