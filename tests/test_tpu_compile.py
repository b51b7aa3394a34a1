"""Compile-only checks for a TPU v5e, at real widths: the Pallas kernels,
and the serving engine's decode program for Phi-3-mini.

The TPU compiler is installed even where no chip is attached: it compiles
for a described v5e topology and refuses what the chip's compiler would
refuse (unsupported primitives, layouts, VMEM over-use), which interpret
mode on the CPU cannot show.  Nothing runs, so these say nothing about
results or speed.  The topology is described inside a module fixture,
never at import time: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import math
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


@pytest.fixture(scope="module")
def phi3_serve_step(shape):
    """``ServeEngine``'s decode program at Phi-3-mini's published widths,
    as the ``phi3-chat-backlog`` cell runs it: 4 slots of 2048 positions,
    a position per slot, the cache donated.  Returns (compiled, cache
    shapes)."""
    import functools

    from repro.configs import ARCHS
    from repro.launch.serve import serve_step
    from repro.models import init_cache, init_params

    cfg, B, T = ARCHS["phi3-mini-3.8b"], 4, 2048

    def on_chip(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, B, T)))
    batch = {"tokens": shape((B, 1), jnp.int32), "last": shape((B,), jnp.int32)}
    compiled = jax.jit(functools.partial(serve_step, cfg=cfg),
                       donate_argnums=(2,)).lower(
        params, batch, cache, shape((B,), jnp.int32)).compile()
    return compiled, cache


def test_phi3_mini_serving_step_compiles_for_v5e_and_fits_one_chip(
        phi3_serve_step):
    """The cache is donated and updated in place, and weights, cache and
    the program's temporaries fit one 16 GB chip.  The ``serve.*`` device
    readers take every op of the traced window, so no op name needs
    pinning."""
    compiled, cache = phi3_serve_step
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes == cache_bytes
    held = mem.argument_size_in_bytes + mem.output_size_in_bytes - \
        mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert held < 16e9


_MOVES = ("copy", "transpose", "dynamic-slice")


def _kv_moves(hlo: str, sizes):
    """The ops of a compiled program that copy, transpose or slice out an
    array of one of ``sizes`` elements: ``copy``, ``transpose`` and
    ``dynamic-slice`` instructions, and fusions the compiler named after
    one (``constant_dynamic-slice_fusion.10``), outside the bodies of
    fusions, where a slice only feeds its consumer and is never
    materialised."""
    comps, fused, cur = {}, set(), None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
            if " fusion(" in line:
                fused.update(re.findall(r"calls=%([\w.\-]+)", line))
    found = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                         r"([\w\-]+)\(", line)
            if not m:
                continue
            op, dims = m.group(1), [int(d) for d in m.group(2).split(",") if d]
            kind = m.group(3) if m.group(3) in _MOVES else next(
                (k for k in _MOVES if m.group(3) == "fusion" and k in op), None)
            if kind and math.prod(dims) in sizes:
                found.append(f"{kind} {op} {dims}")
    return found


def test_phi3_mini_serving_step_writes_the_cache_in_place(phi3_serve_step):
    """Each decode step writes one row a slot into each layer's K/V where
    it lies: the program holds less than one layer's K plus V in
    temporaries (a layer's K and V are 100,663,296 bytes), and copies,
    transposes or slices out no whole K/V stack or layer.  A scan that
    took the cache as ``xs`` and returned it as ``ys`` held 3.46 GB of
    temporaries, sliced every layer out, copied it around the row
    writes and copied the restacked cache into the donated buffer."""
    compiled, cache = phi3_serve_step
    k = cache["k"]
    layer_bytes = 2 * (k.size // k.shape[0]) * k.dtype.itemsize
    assert layer_bytes == 100_663_296
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    assert _kv_moves(compiled.as_text(), {k.size, k.size // k.shape[0]}) == []
