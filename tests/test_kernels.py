"""Per-kernel shape/dtype sweeps: pallas(interpret=True) vs ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

K = jax.random.key(7)


def _k(i):
    return jax.random.fold_in(K, i)


def _rand(i, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(_k(i), shape) * scale).astype(dtype)


@pytest.mark.parametrize("bh,s,dh,hid", [(2, 32, 16, 4), (3, 64, 32, 8),
                                         (1, 128, 64, 16), (2, 64, 32, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlp_softmax_attn_sweep(bh, s, dh, hid, dtype):
    q, k, v = (_rand(i, (bh, s, dh), dtype) for i in range(3))
    w1 = _rand(3, (s, hid), scale=0.2)
    b1 = _rand(4, (hid,), scale=0.1)
    w2 = _rand(5, (hid, s), scale=0.2)
    b2 = _rand(6, (s,), scale=0.01)
    got = ops.mlp_softmax_attn(q, k, v, w1, b1, w2, b2, impl="interpret",
                               bq=16, bk=16)
    want = ref.mlp_softmax_attn(q, k, v, w1, b1, w2, b2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert err < tol * max(1.0, float(jnp.abs(want).max())), err


def test_mlp_softmax_attn_block_shape_invariance():
    """Different BlockSpec tilings must give identical results."""
    q, k, v = (_rand(i, (2, 64, 32)) for i in range(3))
    w1, b1 = _rand(3, (64, 8), scale=0.2), _rand(4, (8,), scale=0.1)
    w2, b2 = _rand(5, (8, 64), scale=0.2), _rand(6, (64,), scale=0.01)
    o1 = ops.mlp_softmax_attn(q, k, v, w1, b1, w2, b2, impl="interpret",
                              bq=16, bk=16)
    o2 = ops.mlp_softmax_attn(q, k, v, w1, b1, w2, b2, impl="interpret",
                              bq=64, bk=32)
    assert np.allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


@pytest.mark.parametrize("bh,sq,skv,dh", [(2, 32, 32, 16), (1, 64, 64, 64),
                                          (3, 32, 64, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attn_sweep(bh, sq, skv, dh, causal):
    if causal and sq != skv:
        pytest.skip("causal requires square here")
    q = _rand(0, (bh, sq, dh))
    k = _rand(1, (bh, skv, dh))
    v = _rand(2, (bh, skv, dh))
    got = ops.flash_attn(q, k, v, causal=causal, impl="interpret",
                         bq=16, bk=16)
    want = ref.flash_attn(q, k, v, causal=causal)
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("r,v", [(16, 64), (32, 512), (64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_entropy_head_sweep(r, v, dtype):
    logits = _rand(0, (r, v), dtype, scale=3.0)
    got = ops.entropy_head(logits, impl="interpret", br=16, bv=32)
    want = ref.entropy_head(logits)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    assert float(jnp.abs(got - want).max()) < tol
    # entropy bounded by log(V)
    assert float(got.max()) <= np.log(v) + 0.1


@pytest.mark.parametrize("b,t,h,p,n,chunk", [(2, 64, 3, 8, 16, 16),
                                             (1, 128, 2, 16, 32, 32),
                                             (2, 32, 1, 8, 8, 8)])
def test_ssd_sweep(b, t, h, p, n, chunk):
    x = _rand(0, (b, t, h, p))
    a = -jnp.abs(_rand(1, (b, t, h), scale=0.2))
    bb = _rand(2, (b, t, n), scale=0.3)
    c = _rand(3, (b, t, n), scale=0.3)
    got = ops.ssd_chunked(x, a, bb, c, chunk=chunk, impl="interpret")
    want = ref.ssd(x, a, bb, c)
    scale = max(1.0, float(jnp.abs(want).max()))
    assert float(jnp.abs(got - want).max()) / scale < 1e-5


def test_ssd_kernel_matches_model_scan():
    """The Pallas kernel and the model-zoo ssd_scan share semantics."""
    from repro.models.ssd import ssd_scan
    b, t, h, p, n = 2, 64, 3, 8, 16
    x = _rand(0, (b, t, h, p))
    a = -jnp.abs(_rand(1, (b, t, h), scale=0.2))
    bb = _rand(2, (b, t, n), scale=0.3)
    c = _rand(3, (b, t, n), scale=0.3)
    y_model, _ = ssd_scan(x, a, bb, c, chunk=16)
    y_kernel = ops.ssd_chunked(x, a, bb, c, chunk=16, impl="interpret")
    assert np.allclose(np.asarray(y_model), np.asarray(y_kernel), atol=1e-4)


@pytest.mark.parametrize("b,t,d,bt", [(2, 64, 16, 16), (1, 128, 32, 32),
                                      (3, 32, 8, 8)])
def test_rg_lru_sweep(b, t, d, bt):
    a = jax.nn.sigmoid(_rand(0, (b, t, d)))
    bb = _rand(1, (b, t, d))
    got = ops.rg_lru_scan(a, bb, impl="interpret", bt=bt)
    want = ref.rg_lru(a, bb)
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("m,k,n", [(16, 32, 8), (32, 64, 32), (8, 128, 16)])
def test_secure_matmul_exact(m, k, n):
    rng = np.random.default_rng(m + k + n)
    eps = jnp.asarray(rng.integers(-2 ** 20, 2 ** 20, (m, k)), jnp.int32)
    dlt = jnp.asarray(rng.integers(-2 ** 20, 2 ** 20, (k, n)), jnp.int32)
    a = jnp.asarray(rng.integers(-2 ** 30, 2 ** 30, (2, m, k)), jnp.int32)
    b = jnp.asarray(rng.integers(-2 ** 30, 2 ** 30, (2, k, n)), jnp.int32)
    c = jnp.asarray(rng.integers(-2 ** 30, 2 ** 30, (2, m, n)), jnp.int32)
    got = ops.secure_matmul(eps, dlt, a, b, c, impl="interpret",
                            bm=8, bn=8, bk=16)
    want = ops.secure_matmul(eps, dlt, a, b, c, impl="ref")
    assert np.array_equal(np.asarray(got), np.asarray(want))


I32_EDGES = np.array([np.iinfo(np.int32).min, -1, np.iinfo(np.int32).max,
                      0, 1, -128, 127, 128, -129], np.int64)


def _edge_ring(rng, shape):
    """Full-range int32 values with the digit split's edge cases mixed
    in: INT32_MIN, -1, INT32_MAX and the byte boundaries."""
    x = rng.integers(np.iinfo(np.int32).min, np.iinfo(np.int32).max,
                     shape, endpoint=True)
    edge = rng.random(shape) < 0.3
    return jnp.asarray(np.where(edge, rng.choice(I32_EDGES, shape), x),
                       jnp.int32)


def test_int8_digits_reconstruct_mod_2_32():
    from repro.kernels.secure_matmul import int8_digits
    x = jnp.asarray(I32_EDGES, jnp.int32)
    d = np.asarray(int8_digits(x)).astype(np.int64)
    assert d.min() >= -128 and d.max() <= 127
    back = sum(d[i] << (8 * i) for i in range(4))
    assert np.array_equal(back % 2 ** 32, I32_EDGES % 2 ** 32)


@pytest.mark.parametrize("m,k,n,blocks", [
    (256, 256, 128, {}),                       # tileable, 128 blocks
    (16, 32, 8, dict(bm=8, bn=8, bk=16)),      # tileable, small blocks
    (8, 64, 2, {}),                            # whole-array blocks
    (24, 40, 20, dict(bm=16, bn=16, bk=16)),   # padded on every dim
    (130, 200, 136, {}),                       # padded past one block
])
def test_secure_matmul_digit_kernel_bitwise(m, k, n, blocks):
    """The int8-digit kernel equals the int32 reference bit for bit on
    full-range operands, edge values included."""
    rng = np.random.default_rng(m * k + n)
    eps, dlt = _edge_ring(rng, (m, k)), _edge_ring(rng, (k, n))
    a, b = _edge_ring(rng, (2, m, k)), _edge_ring(rng, (2, k, n))
    c = _edge_ring(rng, (2, m, n))
    got = ops.secure_matmul(eps, dlt, a, b, c, impl="interpret", **blocks)
    want = jnp.stack([ref.secure_matmul_combine(eps, dlt, a[p], b[p], c[p], p)
                      for p in (0, 1)])
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_secure_matmul_implements_beaver():
    """Kernel combine + reconstruction == plain ring matmul x@y."""
    rng = np.random.default_rng(0)
    m, kdim, n = 8, 16, 8
    x = rng.integers(-2 ** 10, 2 ** 10, (m, kdim)).astype(np.int32)
    y = rng.integers(-2 ** 10, 2 ** 10, (kdim, n)).astype(np.int32)
    a = rng.integers(-2 ** 30, 2 ** 30, (m, kdim)).astype(np.int32)
    b = rng.integers(-2 ** 30, 2 ** 30, (kdim, n)).astype(np.int32)
    c = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.int32)  # wraps
    # share everything
    a_sh = np.stack([rng.integers(-2 ** 31, 2 ** 31, a.shape),
                     np.zeros_like(a)]).astype(np.int32)
    a_sh[1] = a - a_sh[0]
    b_sh = np.stack([rng.integers(-2 ** 31, 2 ** 31, b.shape),
                     np.zeros_like(b)]).astype(np.int32)
    b_sh[1] = b - b_sh[0]
    c_sh = np.stack([rng.integers(-2 ** 31, 2 ** 31, c.shape),
                     np.zeros_like(c)]).astype(np.int32)
    c_sh[1] = c - c_sh[0]
    eps = (x - a).astype(np.int32)
    dlt = (y - b).astype(np.int32)
    z_sh = ops.secure_matmul(jnp.asarray(eps), jnp.asarray(dlt),
                             jnp.asarray(a_sh), jnp.asarray(b_sh),
                             jnp.asarray(c_sh), impl="interpret",
                             bm=8, bn=8, bk=16)
    z = np.asarray(z_sh[0] + z_sh[1])
    want = (x.astype(np.int64) @ y.astype(np.int64)).astype(np.int32)
    assert np.array_equal(z, want)
