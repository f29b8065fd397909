"""Per-kernel validation: Pallas (interpret mode) and chunked-jnp variants
against the pure-jnp oracles, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def arr(*shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# --------------------------------------------------------- flash attention --
ATTN_SHAPES = [
    # B, Hq, Hkv, Sq, Sk, D
    (1, 1, 1, 128, 128, 32),
    (2, 4, 2, 128, 128, 64),
    (2, 8, 1, 256, 256, 32),    # MQA
    (1, 6, 2, 128, 256, 32),    # cross/decode-ish Sq < Sk
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_pallas(shape, dtype, causal):
    B, Hq, Hkv, Sq, Sk, D = shape
    if causal and Sq != Sk:
        pytest.skip("causal offset covered separately")
    q, k, v = arr(B, Hq, Sq, D, dtype=dtype), arr(B, Hkv, Sk, D, dtype=dtype), \
        arr(B, Hkv, Sk, D, dtype=dtype)
    got = ops.attention(q, k, v, causal=causal, impl="pallas", interpret=True)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("block_k", [32, 64, 128])
def test_attention_chunked_blocks(block_k):
    q, k, v = arr(2, 4, 128, 32), arr(2, 2, 128, 32), arr(2, 2, 128, 32)
    got = ref.attention_chunked(q, k, v, causal=True, block_k=block_k)
    want = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_attention_chunked_flash_backward():
    q, k, v = arr(2, 4, 128, 16), arr(2, 2, 128, 16), arr(2, 2, 128, 16)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.attention(q, k, v, causal=True)))

    def loss_chunk(q, k, v):
        return jnp.sum(jnp.sin(
            ref.attention_chunked(q, k, v, causal=True, block_k=32)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_chk = jax.grad(loss_chunk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_chk):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


def test_attention_softcap():
    q, k, v = arr(1, 2, 64, 16), arr(1, 2, 64, 16), arr(1, 2, 64, 16)
    got = ops.attention(q, k, v, causal=True, logit_softcap=30.0,
                        impl="pallas", interpret=True)
    want = ref.attention(q, k, v, causal=True, logit_softcap=30.0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shape,causal,softcap", [
    ((1, 2, 1, 128, 128, 32), True, 0.0),       # GQA, one q block
    ((1, 4, 2, 256, 256, 32), True, 0.0),       # several q and kv blocks
    ((1, 2, 2, 128, 256, 32), False, 0.0),      # Sq < Sk, no mask
    ((1, 2, 1, 128, 128, 32), True, 30.0),      # logit softcap
])
def test_flash_attention_pallas_grad(shape, causal, softcap):
    """The Pallas kernel's custom_vjp (kernel forward + blockwise jnp
    backward) against autodiff of the plain reference."""
    B, Hq, Hkv, Sq, Sk, D = shape
    q, k, v = arr(B, Hq, Sq, D), arr(B, Hkv, Sk, D), arr(B, Hkv, Sk, D)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    pallas = lambda q, k, v: ops.attention(
        q, k, v, causal=causal, logit_softcap=softcap, impl="pallas",
        interpret=True)
    oracle = lambda q, k, v: ref.attention(
        q, k, v, causal=causal, logit_softcap=softcap)
    got = jax.grad(loss(pallas), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


# --------------------------------------------------------- decode attention --
DEC_SHAPES = [(1, 1, 1, 128, 32), (2, 4, 2, 256, 64), (2, 8, 1, 512, 32)]


@pytest.mark.parametrize("shape", DEC_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_pallas(shape, dtype):
    B, Hq, Hkv, S, D = shape
    q = arr(B, Hq, D, dtype=dtype)
    k, v = arr(B, Hkv, S, D, dtype=dtype), arr(B, Hkv, S, D, dtype=dtype)
    kv_len = jnp.asarray(RNG.integers(1, S + 1, B), jnp.int32)
    got = ops.decode_attention(q, k, v, kv_len=kv_len, impl="pallas",
                               interpret=True)
    want = ref.decode_attention(q, k, v, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


# ---------------------------------------------------------- chunk attention --
CHUNK_SHAPES = [
    # B, Hq, Hkv, T, S, D
    (1, 1, 1, 4, 128, 32),
    (2, 4, 2, 8, 256, 64),
    (2, 8, 1, 16, 512, 32),    # MQA, multi-block cache
    (1, 8, 1, 96, 256, 32),    # g*T = 768 rows: three 256-row blocks
]


@pytest.mark.parametrize("shape", CHUNK_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_attention_pallas(shape, dtype):
    """Offset-causal positioned-chunk kernel vs the oracle at mixed
    per-row offsets (each row's chunk lands at its own cache depth)."""
    B, Hq, Hkv, T, S, D = shape
    q = arr(B, Hq, T, D, dtype=dtype)
    k, v = arr(B, Hkv, S, D, dtype=dtype), arr(B, Hkv, S, D, dtype=dtype)
    pos = jnp.asarray(RNG.integers(0, S - T + 1, B), jnp.int32)
    got = ops.chunk_attention(q, k, v, pos=pos, impl="pallas",
                              interpret=True)
    want = ref.chunk_attention(q, k, v, pos=pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


def test_chunk_attention_width1_is_decode():
    """T == 1 at offset pos must match decode attention with
    kv_len = pos + 1 — prefill and decode are one operation."""
    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    q = arr(B, Hq, 1, D)
    k, v = arr(B, Hkv, S, D), arr(B, Hkv, S, D)
    pos = jnp.asarray([5, 77], jnp.int32)
    chunk = ref.chunk_attention(q, k, v, pos=pos)
    dec = ref.decode_attention(q[:, :, 0], k, v, kv_len=pos + 1)
    np.testing.assert_allclose(chunk[:, :, 0], dec, atol=2e-5, rtol=2e-5)


def test_chunk_attention_blocked_matches_oracle():
    q, k, v = arr(2, 4, 8, 32), arr(2, 2, 256, 32), arr(2, 2, 256, 32)
    pos = jnp.asarray([3, 200], jnp.int32)
    got = ref.chunk_attention_blocked(q, k, v, pos=pos, block_k=64)
    want = ref.chunk_attention(q, k, v, pos=pos)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_chunk_attention_ignores_stale_cache_past_frontier():
    """Columns beyond pos + t must get exactly-zero mass: poisoning them
    with huge values may not change the output (a serving slot's row
    holds a neighbour request's stale K/V past its own frontier)."""
    B, Hq, Hkv, T, S, D = 1, 2, 2, 4, 64, 16
    q = arr(B, Hq, T, D)
    k, v = arr(B, Hkv, S, D), arr(B, Hkv, S, D)
    pos = jnp.asarray([10], jnp.int32)
    clean = ref.chunk_attention(q, k, v, pos=pos)
    k_bad = k.at[:, :, 20:].set(1e4)
    v_bad = v.at[:, :, 20:].set(-1e4)
    poisoned = ref.chunk_attention(q, k_bad, v_bad, pos=pos)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(poisoned))


def test_decode_attention_residuals_combine():
    """Split-K: shard the KV, merge partials == unsharded decode."""
    B, Hq, Hkv, S, D = 2, 4, 2, 256, 32
    q = arr(B, Hq, D)
    k, v = arr(B, Hkv, S, D), arr(B, Hkv, S, D)
    full = ref.decode_attention(q, k, v)
    n_shards = 4
    o_parts, m_parts, l_parts = [], [], []
    for i in range(n_shards):
        sl = slice(i * S // n_shards, (i + 1) * S // n_shards)
        o, (m, l) = ref.decode_attention(q, k[:, :, sl], v[:, :, sl],
                                         return_residuals=True)
        o_parts.append(o)
        m_parts.append(m)
        l_parts.append(l)
    merged = ref.combine_decode_partials(
        jnp.stack(o_parts), jnp.stack(m_parts), jnp.stack(l_parts))
    np.testing.assert_allclose(merged, full, atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------- rmsnorm --
@pytest.mark.parametrize("rows,d", [(1, 64), (37, 128), (256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas(rows, d, dtype):
    x, w = arr(rows, d, dtype=dtype), arr(d, dtype=dtype)
    got = ops.rmsnorm(x, w, impl="pallas", interpret=True)
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


def test_rmsnorm_add_pallas():
    x, r, w = arr(64, 128), arr(64, 128), arr(128)
    y1, s1 = ops.rmsnorm_add(x, r, w, impl="pallas", interpret=True)
    y2, s2 = ops.rmsnorm_add(x, r, w, impl="ref")
    np.testing.assert_allclose(y1, y2, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s1, s2, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_pallas_grad(dtype):
    x, w = arr(2, 37, 128, dtype=dtype), arr(128, dtype=dtype)
    g = arr(2, 37, 128, dtype=dtype)

    def vjp(fn):
        return jax.vjp(fn, x, w)[1](g)

    got = vjp(lambda x, w: ops.rmsnorm(x, w, impl="pallas", interpret=True))
    want = vjp(lambda x, w: ref.rmsnorm(x, w))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5 * tol(dtype), rtol=5 * tol(dtype))


def test_rmsnorm_add_pallas_grad():
    x, r, w = arr(64, 128), arr(64, 128), arr(128)
    gy, gs = arr(64, 128), arr(64, 128)

    def vjp(impl, **kw):
        fn = lambda x, r, w: ops.rmsnorm_add(x, r, w, impl=impl, **kw)
        return jax.vjp(fn, x, r, w)[1]((gy, gs))

    for a, b in zip(vjp("pallas", interpret=True), vjp("ref")):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-4)


# ---------------------------------------------------------------- ssd scan --
SSD_SHAPES = [(1, 64, 1, 16, 8, 32), (2, 128, 3, 32, 16, 32),
              (1, 96, 2, 16, 8, 32)]  # B, L, H, P, N, chunk


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_chunked_vs_naive(shape):
    B, L, H, P, N, chunk = shape
    x = arr(B, L, H, P)
    dt = jnp.abs(arr(B, L, H)) * 0.1
    a = -jnp.abs(arr(H))
    b, c = arr(B, L, N), arr(B, L, N)
    y1, h1 = ref.ssd_naive(x, dt, a, b, c)
    y2, h2 = ref.ssd_chunked(x, dt, a, b, c, chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(h1, h2, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("shape", SSD_SHAPES[:2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_pallas(shape, dtype):
    B, L, H, P, N, chunk = shape
    x = arr(B, L, H, P, dtype=dtype)
    dt = jnp.abs(arr(B, L, H)) * 0.1
    a = -jnp.abs(arr(H))
    b, c = arr(B, L, N, dtype=dtype), arr(B, L, N, dtype=dtype)
    y1, h1 = ref.ssd_naive(x, dt, a, b, c)
    y2, h2 = ops.ssd_scan(x, dt, a, b, c, chunk=chunk, impl="pallas",
                          interpret=True)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=tol(dtype), rtol=20 * tol(dtype))
    np.testing.assert_allclose(h1, h2, atol=tol(dtype), rtol=20 * tol(dtype))


def test_ssd_pad_to_chunk():
    """ops.ssd_scan pads L to a chunk multiple without changing results."""
    B, L, H, P, N = 1, 50, 2, 8, 4
    x = arr(B, L, H, P)
    dt = jnp.abs(arr(B, L, H)) * 0.1
    a = -jnp.abs(arr(H))
    b, c = arr(B, L, N), arr(B, L, N)
    y1, h1 = ref.ssd_naive(x, dt, a, b, c)
    y2, h2 = ops.ssd_scan(x, dt, a, b, c, chunk=16, impl="ref")
    np.testing.assert_allclose(y1, y2, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(h1, h2, atol=2e-5, rtol=2e-4)


def test_ssd_state_handoff():
    """Final state from a prefix + ssd_naive(h0=...) == full run."""
    B, L, H, P, N = 1, 64, 2, 8, 4
    x = arr(B, L, H, P)
    dt = jnp.abs(arr(B, L, H)) * 0.1
    a = -jnp.abs(arr(H))
    b, c = arr(B, L, N), arr(B, L, N)
    y_full, h_full = ref.ssd_naive(x, dt, a, b, c)
    _, h_half = ref.ssd_chunked(x[:, :32], dt[:, :32], a, b[:, :32],
                                c[:, :32], chunk=16)
    y2, h2 = ref.ssd_naive(x[:, 32:], dt[:, 32:], a, b[:, 32:], c[:, 32:],
                           h0=h_half)
    np.testing.assert_allclose(y_full[:, 32:], y2, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(h_full, h2, atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_ssd_h0_resume_matches_full_run(impl):
    """ops.ssd_scan(h0=...) — the chunked-prefill resume path — run over
    two half-prompts equals one full-prompt scan, for both the oracle and
    the Pallas kernel (interpret mode)."""
    B, L, H, P, N = 2, 64, 2, 16, 8
    x = arr(B, L, H, P)
    dt = jnp.abs(arr(B, L, H)) * 0.1
    a = -jnp.abs(arr(H))
    b, c = arr(B, L, N), arr(B, L, N)
    kw = dict(chunk=16, impl=impl)
    if impl == "pallas":
        kw["interpret"] = True
    y_full, h_full = ops.ssd_scan(x, dt, a, b, c, **kw)
    y1, h1 = ops.ssd_scan(x[:, :32], dt[:, :32], a, b[:, :32], c[:, :32],
                          **kw)
    y2, h2 = ops.ssd_scan(x[:, 32:], dt[:, 32:], a, b[:, 32:], c[:, 32:],
                          h0=h1, **kw)
    np.testing.assert_allclose(y_full[:, 32:], y2, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(h_full, h2, atol=2e-5, rtol=2e-4)
