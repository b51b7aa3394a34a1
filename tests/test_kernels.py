"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode), plus hypothesis property tests for Smith-Waterman."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.ops import AA_ALPHABET, BLOSUM50, build_profile


# --------------------------------------------------------------------------
# Smith-Waterman
# --------------------------------------------------------------------------
def test_blosum50_symmetric():
    m = np.asarray(BLOSUM50)
    assert m.shape == (24, 24)
    assert np.array_equal(m, m.T)
    assert m[0, 0] == 5 and m[4, 4] == 13  # A-A=5, C-C=13


def test_sw_known_alignment():
    """Identical sequences: score == sum of diagonal substitution scores."""
    seq = ops.encode_seq("HEAGAWGHEE")
    diag = float(sum(BLOSUM50[c, c] for c in np.asarray(seq)))
    got = float(ops.smith_waterman(seq, seq, tile=64))
    assert got == diag


def test_sw_empty_overlap_zero():
    a = ops.encode_seq("AAAA")
    b = ops.encode_seq("WWWW")  # A-W = -3: no positive local alignment
    assert float(ops.smith_waterman(a, b, tile=64)) == 0.0


@pytest.mark.parametrize("gaps", [(10.0, 2.0), (5.0, 2.0)])  # paper's two regimes
@pytest.mark.parametrize("qlen,dlen", [(7, 13), (30, 64), (64, 200), (129, 70)])
def test_sw_matches_sequential_ref(gaps, qlen, dlen):
    go, ge = gaps
    rng = np.random.default_rng(qlen * dlen)
    q = jnp.asarray(rng.integers(0, 20, qlen), jnp.int32)
    d = jnp.asarray(rng.integers(0, 20, dlen), jnp.int32)
    got = float(ops.smith_waterman(q, d, gap_open=go, gap_extend=ge, tile=64))
    prof, _ = build_profile(q)
    want = float(ref.sw_ref(prof, d, go, ge))
    assert got == want


@given(st.integers(1, 25), st.integers(1, 40), st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_sw_property_triple_check(qlen, dlen, seed):
    """pallas == sequential-jax-ref == cell-by-cell numpy, random cases."""
    rng = np.random.default_rng(seed)
    q = rng.integers(0, 20, qlen)
    d = rng.integers(0, 20, dlen)
    m = np.asarray(BLOSUM50)
    got = float(ops.smith_waterman(jnp.asarray(q, jnp.int32),
                                   jnp.asarray(d, jnp.int32), tile=64))
    qs = "".join(AA_ALPHABET[i] for i in q)
    ds = "".join(AA_ALPHABET[i] for i in d)
    want = ref.sw_numpy(qs, ds,
                        lambda a, b: float(m[AA_ALPHABET.index(a), AA_ALPHABET.index(b)]),
                        10.0, 2.0)
    assert got == want


def test_sw_tile_invariance():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.integers(0, 20, 40), jnp.int32)
    d = jnp.asarray(rng.integers(0, 20, 300), jnp.int32)
    scores = {t: float(ops.smith_waterman(q, d, tile=t)) for t in (64, 128, 256)}
    assert len(set(scores.values())) == 1, scores


# --------------------------------------------------------------------------
# Flash attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,T,D", [
    (1, 2, 2, 64, 64, 16),
    (2, 4, 2, 96, 160, 32),   # GQA + ragged
    (1, 8, 1, 128, 128, 64),  # MQA
])
def test_flash_attention_sweep(dtype, B, H, Hkv, S, T, D):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(B * S + T), 3)
    q = jax.random.normal(k1, (B, H, S, D), dtype)
    k = jax.random.normal(k2, (B, Hkv, T, D), dtype)
    v = jax.random.normal(k3, (B, Hkv, T, D), dtype)
    got = ops.flash_attention_op(q, k, v, causal=True, bq=32, bk=64)
    want = ref.attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [16, 48])
def test_flash_attention_sliding_window(window):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (1, 2, 96, 16))
    k = jax.random.normal(k2, (1, 2, 96, 16))
    v = jax.random.normal(k3, (1, 2, 96, 16))
    got = ops.flash_attention_op(q, k, v, causal=True, window=window, bq=32, bk=32)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_matches_model_chunked_path():
    """Pallas kernel vs the model's pure-jnp chunked attention (the path the
    dry-run lowers): same math, two implementations."""
    from repro.models.attention import attention as model_attn
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    B, H, Hkv, S, D = 2, 4, 2, 256, 32
    q = jax.random.normal(k1, (B, S, H, D))
    k = jax.random.normal(k2, (B, S, Hkv, D))
    v = jax.random.normal(k3, (B, S, Hkv, D))
    got_model = model_attn(q, k, v, causal=True, impl="chunked",
                           q_chunk=64, kv_chunk=64)
    got_kernel = ops.flash_attention_op(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, bq=64, bk=64).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got_model), np.asarray(got_kernel),
                               atol=3e-5)


# --------------------------------------------------------------------------
# Decode attention over the serving cache
# --------------------------------------------------------------------------
# (name, lead axes, at, T, heads, KV heads, head dim, positions, dtype):
# f32 caches of T 1024 and kv·dh 128 or 384 take blocks of 512 positions
_DECODE_CASES = [
    ("edges_0_blk-1_blk_T-1", (2,), (1,), 1024, 4, 4, 32, [0, 511, 512, 1023],
     jnp.float32),
    ("ragged", (3,), (2,), 1024, 4, 4, 32, [3, 700, 64, 1000], jnp.float32),
    ("rolling_full", (2,), (0,), 512, 4, 4, 32, [512, 700, 2000, 511],
     jnp.float32),
    ("gqa_head96", (2,), (1,), 1024, 8, 4, 96, [0, 95, 600, 1023],
     jnp.float32),
    ("vlm_two_level_at", (2, 3), (1, 2), 1024, 4, 4, 32, [10, 513, 0, 900],
     jnp.float32),
    ("bf16_head96", (2,), (1,), 512, 4, 4, 96, [5, 127, 128, 300],
     jnp.bfloat16),
]


@pytest.mark.parametrize("case", _DECODE_CASES, ids=[c[0] for c in _DECODE_CASES])
def test_decode_attention_kernel_matches_the_reference(case):
    """The kernel, given the whole stacks and ``at``, equals
    ``cached_decode_attention`` and ``decode_attention`` over the layer.
    Above each slot's position the stacks hold NaN keys and huge values,
    and past its last block NaN values too, as a recycled slot holds
    stale entries: none of it may reach the output."""
    from repro.models.attention import cached_decode_attention, decode_attention
    _, lead, at, T, H, hkv, dh, positions, dtype = case
    B, C = len(positions), hkv * dh
    blk = ops.kv_block(T, C, jnp.dtype(dtype).itemsize)
    assert blk is not None and T % blk == 0
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(T + C), 3)
    q = jax.random.normal(k1, (B, 1, H, dh), dtype)
    k = jax.random.normal(k2, (*lead, B, T, C), dtype)
    v = jax.random.normal(k3, (*lead, B, T, C), dtype)
    pos = jnp.asarray(positions, jnp.int32)
    t = jnp.arange(T)[None, :, None]
    above = t > pos[:, None, None]                       # (B, T, 1)
    unread = t >= blk * (jnp.minimum(pos, T - 1) // blk + 1)[:, None, None]
    kp = jnp.where(above, jnp.nan, k)
    vp = jnp.where(unread, jnp.nan, jnp.where(above, 1e30, v))
    got = np.asarray(ops.decode_attention_op(q, kp, vp, at, pos), np.float32)
    want = cached_decode_attention(q, k[at], v[at], pos)
    split = decode_attention(q, k[at].reshape(B, T, hkv, dh),
                             v[at].reshape(B, T, hkv, dh), pos)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert got.shape == (B, 1, H, dh)
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(got, np.asarray(split, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("T,C,itemsize,blk", [
    (2048, 3072, 2, 256),     # Phi-3-mini: 512 would take 12 MiB of VMEM
    (2048, 1024, 2, 512),
    (1024, 128, 4, 512),
    (384, 3072, 2, 128),      # 256 does not divide 384
    (2048, 96, 2, None),      # kv·dh not whole lanes
    (64, 128, 4, None),       # shorter than a block
])
def test_kv_block_rule(T, C, itemsize, blk):
    """One rule for the kernel's tiling and the engine's
    ``serve.kv_blocks_read``: blocks a slot reads are those up to its
    position, every block once a rolling cache is full.  The kernel
    refuses a cache the rule does not tile."""
    assert ops.kv_block(T, C, itemsize) == blk
    if blk is None:
        dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
        kv = jnp.zeros((1, 1, T, C), dtype)
        with pytest.raises(ValueError):
            ops.decode_attention_op(jnp.zeros((1, 1, 1, C), dtype), kv, kv,
                                    (0,), jnp.zeros((1,), jnp.int32))
    else:
        assert [ops.kv_blocks_read(p, T, blk)
                for p in (0, blk - 1, blk, T - 1, T, 5 * T)] == \
            [1, 1, 2, T // blk, T // blk, T // blk]


# --------------------------------------------------------------------------
# SSD scan
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,T,H,P,N,chunk", [
    (1, 32, 2, 8, 16, 8),
    (2, 64, 3, 8, 16, 16),
    (1, 128, 4, 16, 32, 32),
])
def test_ssd_scan_sweep(dtype, b, T, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(T + H), 5)
    x = jax.random.normal(ks[0], (b, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (b, T, N), dtype)
    C = jax.random.normal(ks[4], (b, T, N), dtype)
    y, h = ops.ssd_scan_op(x, dt, A, B, C, chunk=chunk)
    y_ref, h_ref = ref.ssd_ref(x.astype(jnp.float32), dt, A,
                               B.astype(jnp.float32), C.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=tol, rtol=tol)


def test_ssd_kernel_matches_model_chunked():
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    b, T, H, P, N = 2, 64, 3, 8, 16
    x = jax.random.normal(ks[0], (b, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (b, T, N))
    C = jax.random.normal(ks[4], (b, T, N))
    y1, h1 = ops.ssd_scan_op(x, dt, A, B, C, chunk=16)
    y2, h2 = ssd_chunked(x, dt, A, B, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)
