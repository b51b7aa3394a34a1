"""Compile-only checks of the Pallas kernels for a TPU v5e, at real widths.

The TPU compiler is installed even where no chip is attached: it compiles
for a described v5e topology and refuses what the chip's compiler would
refuse (unsupported primitives, layouts, VMEM over-use), which interpret
mode on the CPU cannot show.  Nothing runs, so these say nothing about
results or speed.  The topology is described inside a module fixture,
never at import time: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.smith_waterman import sw_pallas
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shape(topo, no_compile_cache):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("q_len", [144, 497, 1000])   # the paper's queries
def test_sw_pallas_compiles_for_v5e(shape, q_len):
    qp = -(-q_len // 128) * 128
    compiled = sw_pallas.lower(
        shape((24, qp), jnp.float32), shape((1024,), jnp.int32),
        gap_open=10.0, gap_extend=2.0, q_len=q_len, tile=512,
        interpret=False).compile()
    _assert_mosaic(compiled)


def test_sw_kernel_op_keeps_the_name_the_benchmark_matches(shape):
    """``sw.kernel_gcups`` finds the kernel's device time by this op name:
    the Pallas custom call inherits the jitted ``sw_pallas`` wrapper's."""
    compiled = ops.smith_waterman_padded.lower(
        shape((1000,), jnp.int32), shape((1024,), jnp.int32),
        shape((24, 24), jnp.float32), gap_open=10.0, gap_extend=2.0,
        tile=512, interpret=False).compile()
    assert re.search(r"%sw_pallas[.\d]* = ", compiled.as_text())


def test_flash_attention_compiles_for_v5e_at_phi3_mini_width(shape):
    qkv = shape((1, 32, 2048, 96), jnp.bfloat16)   # 32 heads, head_dim 96
    compiled = flash_attention.lower(qkv, qkv, qkv, causal=True,
                                     interpret=False).compile()
    _assert_mosaic(compiled)


def test_ssd_scan_compiles_for_v5e_at_mamba2_130m_width(shape):
    # d_inner 1536 = 24 heads x head_dim 64, d_state 128, chunk 256
    b, T, H, P, N = 1, 2048, 24, 64, 128
    compiled = ssd_scan.lower(
        shape((b, T, H, P), jnp.bfloat16), shape((b, T, H), jnp.float32),
        shape((H,), jnp.float32), shape((b, T, N), jnp.bfloat16),
        shape((b, T, N), jnp.bfloat16), chunk=256, interpret=False).compile()
    _assert_mosaic(compiled)
