"""Compile-only guards for the main path at real widths on TPU v5e.

Each test compiles for a v5e:2x2 chip that is described, not attached:
nothing runs, but the TPU compiler refuses here what it would refuse on
the chip (Pallas block shapes off the (8, 128) tiling, layouts Mosaic
cannot build, programs that do not fit the chip's memory).  The topology
is described inside a fixture, never at import: only one process at a
time may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.decode_attention.kernel import decode_attention_grouped
from repro.pde import mpdata

HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compile can be written to the persistent cache but
    # never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _bytes_per_chip(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("mask_form", ["shared", "per_sequence"])
def test_decode_kernel_compiles_at_yi6b_widths(one_chip, mask_form):
    """yi-6b decode: B=8, KH=4, G=8, D=128, S=2048, bf16 — the shared (S,)
    mask and the paged engine's per-sequence (B, S) mask."""
    b, kh, g, d, s = 8, 4, 8, 128, 2048
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = sds((b, kh, g, d), jnp.bfloat16)
    kv = sds((b, kh, s, d), jnp.bfloat16)
    mask = sds((s,) if mask_form == "shared" else (b, s), jnp.bool_)
    compiled = jax.jit(decode_attention_grouped).lower(q, kv, kv,
                                                       mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,grid", [(8192, (1, 1)), (16384, (2, 2))])
def test_mpdata_block_compiles(topo, n, grid):
    """The solver's whole time block (10 steps) at chip_smoke's fields: one
    chip at 8192², and 16384² decomposed over the 2x2 mesh, where the halo
    exchange must lower to collective-permutes."""
    devs = np.array(topo.devices[:grid[0] * grid[1]]).reshape(grid)
    mesh = Mesh(devs, ("x", "y"), axis_types=(AxisType.Auto,) * 2)
    solve = mpdata.make_solver(mesh, inner_steps=10)
    psi = jax.ShapeDtypeStruct((n, n), jnp.float32,
                               sharding=NamedSharding(mesh, P("x", "y")))
    compiled = jax.jit(lambda p: solve(p)).lower(psi).compile()
    assert _bytes_per_chip(compiled) < HBM_BYTES
    permutes = compiled.as_text().count("collective-permute-start")
    if grid == (1, 1):
        assert permutes == 0
    else:
        assert permutes > 0
