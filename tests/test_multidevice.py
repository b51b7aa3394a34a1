"""Multi-device semantics tests.

Device count is locked at first jax init, so these run in SUBPROCESSES with
XLA_FLAGS=--xla_force_host_platform_device_count=8 while the main pytest
session keeps the real single CPU device.  All mesh/shard_map construction
goes through ``repro.compat``, the one module that spells the JAX 0.9
sharding API (``AxisType.Auto`` meshes, top-level ``jax.shard_map``).
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Genuinely environment-dependent, not version-dependent: the subprocesses
# force 8 *host* (CPU) devices, which only takes effect when the CPU backend
# is the default — on a GPU/TPU container jax would pick that backend and
# the (8,) meshes would want 8 physical accelerators.
pytestmark = pytest.mark.skipif(
    jax.default_backend() != "cpu",
    reason="subprocess tests force 8 host devices via "
           "--xla_force_host_platform_device_count, which only applies to "
           f"the CPU backend (default backend here: {jax.default_backend()!r})",
)


def run_sub(code: str, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["REPRO_DRYRUN_WIRE"] = "f16"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr[-3000:]}"
    return p.stdout


def test_dispatch_combine_roundtrip_and_ring_equivalence():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.core import dispatch, combine
        mesh = make_mesh((8,), ("w",))
        items = jnp.arange(64*4, dtype=jnp.float32).reshape(64, 4)
        dest = (jnp.arange(64) * 7 % 8).astype(jnp.int32)
        def f(backend):
            def body(it, de):
                recv, info = dispatch(it, de, "w", capacity=16, backend=backend)
                return combine(recv * 2.0, info, "w", backend=backend)
            return jax.jit(shard_map(body, mesh=mesh, in_specs=(P("w"), P("w")),
                                         out_specs=P("w")))(items, dest)
        a2a = np.asarray(f("a2a")); ring = np.asarray(f("ring"))
        np.testing.assert_allclose(a2a, np.asarray(items)*2.0)
        np.testing.assert_allclose(ring, a2a)
        print("dispatch ok")
    """)


def test_moe_sharded_matches_single_device_oracle():
    """The full-manual sharded MoE (ep and tp layouts) must equal the
    single-device dense oracle."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.config import ModelConfig
        from repro.models.moe import moe_apply, moe_init
        from repro.models import model as M
        from repro.parallel.context import mesh_context
        from repro.launch.mesh import make_test_mesh

        for E, name in [(8, "ep"), (6, "tp")]:
            cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                              n_heads=4, n_kv_heads=4, d_ff=16, vocab_size=64,
                              n_experts=E, top_k=2, capacity_factor=8.0,
                              pad_heads_to=0, pad_vocab_to=0, dtype="float32")
            params = {"moe": moe_init(jax.random.PRNGKey(0), cfg),
                      "norm2": jnp.ones((32,), jnp.float32)}
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32), jnp.float32)
            from repro.models.layers import rms_norm
            hn = rms_norm(x, params["norm2"], cfg.norm_eps)
            want, _ = moe_apply(hn, params["moe"], cfg,
                                axis_name=None, backend="dense")
            want = x + want
            mesh = make_test_mesh(2, 4)
            with mesh_context(mesh):
                got, aux = jax.jit(lambda xx, pp: M._ffn_part(pp, xx, cfg))(x, params)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4, rtol=1e-4)
            print("moe", name, "ok")
    """)


def test_sharded_loss_matches_single_device():
    """Same params/batch: loss on a 2×4 mesh == loss on 1 device."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS
        from repro.models import init_params, loss_fn
        from repro.parallel.context import mesh_context
        from repro.launch.mesh import make_test_mesh
        for arch in ["phi3-mini-3.8b", "mixtral-8x7b", "mamba2-130m"]:
            cfg = ARCHS[arch].smoke().replace(capacity_factor=8.0)
            params = init_params(cfg, jax.random.PRNGKey(0))
            batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
                     "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)}
            l1, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
            mesh = make_test_mesh(2, 4)
            with mesh_context(mesh):
                l8, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
            np.testing.assert_allclose(float(l1), float(l8), rtol=3e-3), arch
            print(arch, float(l1), float(l8), "ok")
    """)


def test_pipeline_skeleton_and_grads():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.core import pipeline_apply, pipeline_utilisation
        mesh = make_mesh((8,), ("stage",))
        M, mb, d = 5, 2, 3
        params = jnp.arange(8, dtype=jnp.float32).reshape(8, 1, 1)
        xs = jnp.ones((M, mb, d))
        def pipe(pl, x):
            return pipeline_apply(lambda p, v: v + p[0], pl, x, axis_name="stage")
        f = jax.jit(shard_map(pipe, mesh=mesh, in_specs=(P("stage"), P()), out_specs=P()))
        out = np.asarray(f(params, xs))
        np.testing.assert_allclose(out, np.full((M, mb, d), 1 + sum(range(8))))
        g = jax.jit(jax.grad(lambda p: jnp.sum(shard_map(pipe, mesh=mesh,
            in_specs=(P("stage"), P()), out_specs=P())(p, xs))))(params)
        np.testing.assert_allclose(np.asarray(g).ravel(), [M*mb*d]*8)
        assert abs(pipeline_utilisation(8, 5) - 5/12) < 1e-9
        print("pipeline ok")
    """)


def test_skeleton_mesh_lowering_nests_pipeline_over_farms():
    """With 8 devices and a 2-stage skeleton, negotiate_stage_axis gives a
    (2, 4) mesh and lower(..., "mesh") streams microbatches through
    pipeline_apply with a farm_map per stage row — the genuinely nested
    device-flavour composition — and still matches the threads backend."""
    run_sub("""
        from repro.core import Farm, Feedback, Pipeline, lower
        f = lambda x: x * 3 + 1
        g = lambda x: x - 7
        skel = Pipeline(Farm(f, 4, ordered=True), Farm(g, 4, ordered=True))
        prog = lower(skel, "mesh", grain=8)
        assert (prog.n_stage, prog.n_worker) == (2, 4), \\
            (prog.n_stage, prog.n_worker)
        xs = list(range(-50, 163))
        out = prog(xs)
        assert out == [g(f(x)) for x in xs] == lower(skel, "threads")(xs)
        fb = Feedback(lambda x: x * 2 + 1, lambda x: x < 64, max_trips=32)
        pfb = lower(fb, "mesh")
        assert pfb.n_worker == 8
        assert pfb(list(range(40))) == lower(fb, "threads")(list(range(40)))
        print("skeleton mesh nested ok")
    """)


def test_ring_attention_matches_reference():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.parallel.ring_attention import ring_attention
        from repro.kernels.ref import attention_ref
        mesh = make_mesh((8,), ("sp",))
        B, S, H, D = 2, 64, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, H, D))
        v = jax.random.normal(ks[2], (B, S, H, D))
        f = jax.jit(shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=True),
            mesh=mesh, in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp")))
        got = np.asarray(f(q, k, v))
        want = np.asarray(attention_ref(q.transpose(0,2,1,3), k.transpose(0,2,1,3),
                                        v.transpose(0,2,1,3), causal=True).transpose(0,2,1,3))
        np.testing.assert_allclose(got, want, atol=2e-5)
        print("ring attention ok")
    """)


def test_ef_int8_psum_compression():
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.compat import make_mesh, shard_map
        from repro.optim import ef_int8_psum
        mesh = make_mesh((8,), ("dp",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
        r0 = jnp.zeros((256,))
        def body(g_loc, r):
            out, r2 = ef_int8_psum({"g": g_loc[0]}, {"g": r}, "dp")
            return out["g"], r2["g"]
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"), P()),
                              out_specs=(P(), P()), check_vma=False))
        approx, resid = f(g, r0)
        exact = np.asarray(g).mean(0)            # ef_int8_psum returns the MEAN
        err = np.abs(np.asarray(approx) - exact).max()
        scale = np.abs(np.asarray(g)).max()
        assert err < scale / 32, (err, scale)   # int8 quantisation error bound
        # error feedback: residual carries what quantisation dropped
        assert np.abs(np.asarray(resid)).max() <= scale / 64
        print("ef-int8 ok", err)
    """)
