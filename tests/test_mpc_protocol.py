"""MPC substrate: protocol correctness + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.mpc import RING64, ops, nonlinear, compare, quickselect
from repro.mpc.sharing import share, reveal
from repro.mpc.comm import ledger_scope
from repro.mpc.ring import RING32, x64_scope
from repro.mpc import beaver

pytestmark = pytest.mark.usefixtures("x64")

K = jax.random.key(42)
TOL = 2.0 / RING64.scale * 4     # a few LSBs of the fixed-point ring


def _k(i):
    return jax.random.fold_in(K, i)


# ---------------------------------------------------------------------------
# sharing
# ---------------------------------------------------------------------------

class TestSharing:
    def test_share_reconstruct_roundtrip(self):
        x = jnp.array([1.5, -2.25, 1000.0, -0.0001, 0.0])
        assert np.allclose(reveal(share(_k(0), x)), x, atol=TOL)

    def test_single_share_is_uniform(self):
        """One share alone must carry no information about the value."""
        x = jnp.full((4096,), 7.25)
        s = share(_k(1), x)
        sh0 = np.asarray(s.sh[0], dtype=np.float64)
        # uniform over the full int64 ring: huge spread, near-zero mean
        assert np.std(sh0) > 2 ** 60
        assert abs(np.mean(sh0 / 2 ** 63)) < 0.1

    def test_different_keys_different_shares(self):
        x = jnp.ones((16,))
        s1, s2 = share(_k(2), x), share(_k(3), x)
        assert not np.array_equal(np.asarray(s1.sh[0]), np.asarray(s2.sh[0]))
        assert np.allclose(reveal(s1), reveal(s2), atol=TOL)


# ---------------------------------------------------------------------------
# linear ops (hypothesis)
# ---------------------------------------------------------------------------

small_floats = st.lists(st.floats(-64, 64, allow_nan=False, width=32),
                        min_size=1, max_size=16)


class TestLinearOps:
    @given(small_floats, small_floats)
    @settings(max_examples=25, deadline=None)
    def test_add_homomorphic(self, xs, ys):
        n = min(len(xs), len(ys))
        x = jnp.array(xs[:n], jnp.float64)
        y = jnp.array(ys[:n], jnp.float64)
        with x64_scope():
            z = reveal(ops.add(share(_k(4), x), share(_k(5), y)))
        assert np.allclose(z, x + y, atol=TOL)

    @given(small_floats, small_floats)
    @example([0.0, 0.0, 22.0], [0.0, 0.0, 0.010169293731451035])
    @settings(max_examples=25, deadline=None)
    def test_mul_beaver(self, xs, ys):
        n = min(len(xs), len(ys))
        x = jnp.array(xs[:n], jnp.float64)
        y = jnp.array(ys[:n], jnp.float64)
        with x64_scope():
            z = reveal(ops.mul(share(_k(6), x), share(_k(7), y), _k(8)))
        # each operand encodes within half an LSB (2**-(f+1)), and the
        # product (x + ex)(y + ey) is off by |x ey| + |y ex| + |ex ey| <=
        # (|x| + |y| + 1) * 2**-(f+1): the error grows with the operands,
        # not with |x*y| (x = 22, y = 0.0102 is 1.5e-4 off), so the
        # tolerance does too, element by element
        tol = TOL * (1 + np.abs(x) + np.abs(y))
        assert np.all(np.abs(np.asarray(z) - np.asarray(x * y)) <= tol)

    def test_matmul(self):
        a = jax.random.normal(_k(9), (5, 7))
        b = jax.random.normal(_k(10), (7, 3))
        z = reveal(ops.matmul(share(_k(11), a), share(_k(12), b), _k(13)))
        assert np.allclose(z, a @ b, atol=1e-3)

    def test_public_ops(self):
        x = jnp.array([1.0, -2.0, 3.0])
        xs = share(_k(14), x)
        assert np.allclose(reveal(ops.add_public(xs, 2.5)), x + 2.5, atol=TOL)
        assert np.allclose(reveal(ops.mul_public(xs, -1.5)), x * -1.5,
                           atol=1e-3)
        assert np.allclose(reveal(ops.mul_public_int(xs, 3)), x * 3, atol=TOL)

    def test_sum_mean(self):
        x = jax.random.normal(_k(15), (4, 8))
        xs = share(_k(16), x)
        assert np.allclose(reveal(ops.sum_(xs, axis=-1)), x.sum(-1), atol=1e-3)
        assert np.allclose(reveal(ops.mean(xs, axis=-1)), x.mean(-1), atol=1e-3)


# ---------------------------------------------------------------------------
# nonlinear baselines
# ---------------------------------------------------------------------------

class TestNonlinear:
    def test_exp(self):
        x = jnp.array([-2.0, -1.0, 0.0, 0.5, 1.0])
        z = reveal(nonlinear.exp(share(_k(20), x), _k(21)))
        assert np.allclose(z, np.exp(x), rtol=0.05, atol=0.02)

    def test_reciprocal(self):
        x = jnp.array([0.25, 0.5, 1.0, 3.0, 7.0])
        z = reveal(nonlinear.reciprocal(share(_k(22), x), _k(23)))
        assert np.allclose(z, 1 / x, rtol=0.02)

    def test_rsqrt(self):
        x = jnp.array([0.25, 1.0, 2.0, 4.0])
        z = reveal(nonlinear.rsqrt(share(_k(24), x), _k(25)))
        assert np.allclose(z, x ** -0.5, rtol=0.1)

    def test_softmax_close_and_normalized(self):
        x = jax.random.normal(_k(26), (3, 8)) * 2
        z = reveal(nonlinear.softmax(share(_k(27), x), _k(28)))
        want = jax.nn.softmax(x, -1)
        assert np.allclose(z, want, atol=0.02)
        assert np.allclose(z.sum(-1), 1.0, atol=0.05)

    def test_entropy_from_logits(self):
        x = jax.random.normal(_k(29), (4, 6)) * 2
        z = reveal(nonlinear.entropy_from_logits(share(_k(30), x), _k(31)))
        p = jax.nn.softmax(x, -1)
        want = -(p * jnp.log(p + 1e-9)).sum(-1)
        assert np.allclose(z, want, atol=0.15)

    def test_layernorm(self):
        x = jax.random.normal(_k(32), (2, 16))
        g = jnp.ones((16,))
        b = jnp.zeros((16,))
        z = reveal(nonlinear.layernorm(share(_k(33), x), g, b, _k(34)))
        mu = x.mean(-1, keepdims=True)
        want = (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5)
        assert np.allclose(z, want, atol=0.2)


# ---------------------------------------------------------------------------
# comparisons / quickselect
# ---------------------------------------------------------------------------

class TestCompare:
    def test_relu(self):
        x = jnp.array([-3.0, -0.5, 0.0, 0.5, 3.0])
        z = reveal(compare.relu(share(_k(40), x), _k(41)))
        assert np.allclose(z, np.maximum(x, 0), atol=1e-3)

    def test_max_matches(self):
        x = jax.random.normal(_k(42), (4, 7))
        z = reveal(compare.max_(share(_k(43), x), axis=-1, key=_k(44)))
        assert np.allclose(z[..., 0], x.max(-1), atol=1e-3)

    def test_comparison_cost_accounted(self):
        x = share(_k(45), jnp.zeros((10,)))
        y = share(_k(46), jnp.ones((10,)))
        with ledger_scope() as led:
            compare.reveal_lt(x, y)
        assert led.rounds == compare.CMP_ROUNDS
        assert led.nbytes == compare.CMP_BYTES * 10

    @given(st.integers(10, 200), st.integers(1, 9))
    @settings(max_examples=10, deadline=None)
    def test_quickselect_exact_topk(self, n, kfrac):
        k = max(1, n * kfrac // 10)
        rng = np.random.default_rng(n * 10 + kfrac)
        scores = jnp.asarray(rng.normal(size=n))
        with x64_scope():
            ss = share(_k(47), scores)
            got = quickselect.top_k_indices(ss, k, seed=0)
        want = np.sort(np.argsort(np.asarray(scores))[-k:])
        assert np.array_equal(np.sort(got), want)

    def test_quickselect_reveals_only_bits(self):
        """The ledger for quickselect must contain only comparison ops."""
        scores = jnp.asarray(np.random.default_rng(0).normal(size=50))
        ss = share(_k(48), scores)
        with ledger_scope() as led:
            quickselect.top_k_indices(ss, 10)
        assert all(r.op.startswith("secure_cmp") for r in led.records)


# ---------------------------------------------------------------------------
# RING32 dealer-assisted truncation
# ---------------------------------------------------------------------------

class TestRing32:
    def test_trunc_pair_mul(self):
        x = jnp.array([1.5, -2.0, 0.25, 3.0], jnp.float32)
        y = jnp.array([2.0, 1.5, -4.0, 0.5], jnp.float32)
        xs = share(_k(50), x, RING32)
        ys = share(_k(51), y, RING32)
        z = reveal(ops.mul(xs, ys, _k(52)))
        assert np.allclose(z, x * y, atol=4.0 / RING32.scale * (1 + 8))

    def test_beaver_triple_consistency(self):
        a, b, c = beaver.mul_triple(_k(53), (32,), RING64)
        av = a.sh[0] + a.sh[1]
        bv = b.sh[0] + b.sh[1]
        cv = c.sh[0] + c.sh[1]
        assert np.array_equal(np.asarray(av * bv), np.asarray(cv))
