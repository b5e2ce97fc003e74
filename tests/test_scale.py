"""Scale-carrying shares: the cross-op deferred-truncation IR.

Contracts (ISSUE 5):
  1. LATTICE — mpc/scale.py's pure decision procedure: pow2 detection,
     the 2f headroom cap, largest-first forced-trunc planning.
  2. METADATA — `Share.fb` is static pytree aux like `proto`: preserved
     by with_sh / layout ops / flatten-unflatten on BOTH protocol
     backends; `reveal` decodes exactly at any carried exponent.
  3. FOLDS — mul_public by ±2**k is free (no records, no rounding);
     negative and general public scalars stay correct.
  4. GUARD — double-mul chains that would overflow RING32 at 3f hit the
     forced-trunc guard (a real dealer trunc fires, values stay right);
     squares and repeated consumers truncate ONCE (the force memo);
     forcing a broadcast bills the pre-broadcast element count
     (lineage); ReLU is truncation-free (bits at exponent 0).
  5. QUICKSELECT — comparisons force to canonical scale before
     reveal_lt: the selected set and the per-wave comparison ledger are
     pinned bitwise against the canonical-input run.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.engine import MPCEngine
from repro.mpc import compare, ops as mops, quickselect, scale
from repro.mpc.comm import ledger_scope
from repro.mpc.ring import RING32, RING64
from repro.mpc.sharing import Share, reveal, share

K = jax.random.key(7)


def _k(i):
    return jax.random.fold_in(K, i)


# ---------------------------------------------------------------------------
# 1. the lattice algebra
# ---------------------------------------------------------------------------

class TestLattice:
    def test_pow2_exponent(self):
        assert scale.pow2_exponent(2.0) == 1
        assert scale.pow2_exponent(0.25) == -2
        assert scale.pow2_exponent(-0.5) == -1
        assert scale.pow2_exponent(1.0) == 0
        assert scale.pow2_exponent(1 / 32) == -5
        for not_pow2 in (1.5, 0.3, 0.0, 3.0, float("inf"), float("nan"),
                         np.ones(3), "x", None):
            assert scale.pow2_exponent(not_pow2) is None, not_pow2

    @pytest.mark.parametrize("f", [12, 16])
    def test_mul_plan(self, f):
        # canonical inputs ride to 2f untruncated
        assert scale.mul_plan(f, f, f) == (0, 0, 2 * f)
        # one deferred operand: exactly its excess is forced
        assert scale.mul_plan(2 * f, f, f) == (f, 0, 2 * f)
        assert scale.mul_plan(f, 2 * f, f) == (0, f, 2 * f)
        # both deferred: both force back to canonical
        assert scale.mul_plan(2 * f, 2 * f, f) == (f, f, 2 * f)
        # a comparison bit (exponent 0) multiplies for free
        assert scale.mul_plan(2 * f, 0, f) == (0, 0, 2 * f)
        # folded exponent above 2f: only the overhang is forced
        assert scale.mul_plan(2 * f + 3, 0, f) == (3, 0, 2 * f)
        # square at equal exponents plans equal shifts (one memoized
        # trunc when the operands are the same object)
        px, py, out = scale.mul_plan(f + 5, f + 5, f)
        assert px == py == 5 and out == 2 * f

    def test_align_target(self):
        f = 12
        assert scale.align_target(f, f, f) == f
        assert scale.align_target(f, f + 5, f) == f + 5        # lift
        assert scale.align_target(2 * f, f, f) == 2 * f
        # equal above-cap exponents pass through (pure reinterpretation)
        assert scale.align_target(2 * f + 5, 2 * f + 5, f) == 2 * f + 5
        # unequal above-cap clamps to the 2f headroom cap
        assert scale.align_target(2 * f, 2 * f + 5, f) == 2 * f

    def test_sum_plan(self):
        a = scale.SUM_ALLOWANCE
        f, bits = RING32.frac_bits, RING32.bits
        # a 2f product summed over 768 terms needs ceil(log2 768) = 10
        # bits of growth: forced to bits - 2 - 10 - a, exactly
        assert scale.sum_plan(2 * f, 768, f, bits) == 24 + 10 + a - 30
        assert 2 * f - scale.sum_plan(2 * f, 768, f, bits) == 20 - a
        # the excess grows with ceil(log2 n), not with n
        assert scale.sum_plan(2 * f, 513, f, bits) \
            == scale.sum_plan(2 * f, 1024, f, bits) \
            == scale.sum_plan(2 * f, 768, f, bits)
        assert scale.sum_plan(2 * f, 256, f, bits) == 24 + 8 + a - 30
        # room to spare: no force; one term never grows
        assert scale.sum_plan(2 * f, 2 ** (30 - 24 - a), f, bits) == 0
        assert scale.sum_plan(2 * f, 1, f, bits) == 0
        # never below canonical f: a canonical input is never forced
        assert scale.sum_plan(f, 768, f, bits) == 0
        assert scale.sum_plan(f, 2 ** 30, f, bits) == 0
        assert scale.sum_plan(f + 3, 2 ** 30, f, bits) == 3
        # the allowance is the dealer truncation's range at the 2f cap
        assert a == bits - 2 - 2 * f
        # RING64 holds a 2f sum over 2**24 terms and a 3f sum over 256;
        # a 3f sum over 768 terms forces by the 2 bits it outgrows
        f64 = RING64.frac_bits
        assert scale.sum_plan(2 * f64, 2 ** 24, f64, RING64.bits) == 0
        assert scale.sum_plan(3 * f64, 256, f64, RING64.bits) == 0
        assert scale.sum_plan(3 * f64, 768, f64, RING64.bits) == 2

    def test_split_reciprocal(self):
        assert scale.split_reciprocal(768) == (8, 3)
        assert scale.split_reciprocal(256) == (8, 1)
        assert scale.split_reciprocal(1) == (0, 1)
        assert scale.split_reciprocal(7) == (0, 7)
        assert scale.split_reciprocal(96) == (5, 3)
        for n in range(1, 300):
            k, m = scale.split_reciprocal(n)
            assert m % 2 == 1 and n == m << k
        with pytest.raises(ValueError):
            scale.split_reciprocal(0)

    def test_odd_reciprocal_encodes_within_its_bound(self):
        """1/m at f bits is off by at most 2**-(f+1), a relative error of
        at most m * 2**-(f+1); 1/768 encoded whole is 6.25% low."""
        f = RING32.frac_bits
        enc = lambda v: round(v * 2 ** f) / 2 ** f      # noqa: E731
        for n in (768, 384, 96, 3072, 640, 192):
            k, m = scale.split_reciprocal(n)
            rel = abs(enc(1 / m) * 2.0 ** -k * n - 1)
            assert rel <= m * 2.0 ** -(f + 1), (n, rel)
        assert enc(1 / 3) == 1365 / 4096
        assert abs(enc(1 / 3) * 3 - 1) < 2.5e-4
        assert abs(enc(1 / 768) * 768 - 1) == pytest.approx(0.0625)


# ---------------------------------------------------------------------------
# 2. scale metadata through the container
# ---------------------------------------------------------------------------

class TestScaleMetadata:
    @pytest.mark.parametrize("proto", ["2pc", "3pc"])
    def test_pytree_roundtrip_preserves_scale(self, proto, x64):
        s = share(_k(0), jnp.ones((2, 3)), RING64, proto)
        z = mops.mul(s, s, _k(1))            # rides at 2f
        leaves, treedef = jax.tree.flatten(z)
        z2 = jax.tree.unflatten(treedef, leaves)
        assert (z2.fb, z2.proto) == (2 * RING64.frac_bits, proto)
        assert np.array_equal(np.asarray(z.sh), np.asarray(z2.sh))

    @pytest.mark.parametrize("proto", ["2pc", "3pc"])
    def test_with_sh_preserves_proto_and_scale(self, proto, x64):
        s = share(_k(2), jnp.ones((4,)), RING64, proto)
        z = mops.mul(s, s, _k(3))
        rebuilt = z.with_sh(-z.sh)
        assert (rebuilt.proto, rebuilt.fb, rebuilt.n_parties) == \
            (proto, 2 * RING64.frac_bits, z.n_parties)

    @pytest.mark.parametrize("proto", ["2pc", "3pc"])
    def test_layout_ops_propagate_scale(self, proto, x64):
        v = np.random.default_rng(0).normal(size=(2, 3, 4)) * 0.5
        eng = MPCEngine(protocol=proto).with_key(_k(4))
        s = share(_k(5), jnp.asarray(v, jnp.float32), RING64, proto)
        z = mops.mul(s, s, _k(6))            # 2f
        want = (v * v)
        for got, ref in (
                (eng.moveaxis(z, -1, 0), np.moveaxis(want, -1, 0)),
                (eng.swapaxes(z, -1, -2), np.swapaxes(want, -1, -2)),
                (eng.reshape(z, (6, 4)), want.reshape(6, 4)),
                (eng.broadcast(eng.reshape(z, (2, 3, 4)), (2, 2, 3, 4)),
                 np.broadcast_to(want, (2, 2, 3, 4)))):
            assert got.fb == 2 * RING64.frac_bits
            assert np.allclose(np.asarray(reveal(got)), ref, atol=1e-3)

    def test_reveal_decodes_at_carried_scale_exactly(self, x64):
        s = share(_k(7), jnp.asarray([1.5, -2.25, 0.125]), RING64)
        z = mops.mul_public(s, 0.25)         # free fold, no rounding
        assert z.fb == RING64.frac_bits + 2
        got = np.asarray(reveal(z))
        assert np.array_equal(got, np.asarray([0.375, -0.5625, 0.03125]))


# ---------------------------------------------------------------------------
# 3. public rescales
# ---------------------------------------------------------------------------

class TestPublicScalars:
    def test_pow2_fold_is_free(self, x64):
        s = share(_k(10), jnp.asarray([2.0, -3.0]), RING64)
        with ledger_scope() as led:
            z = mops.mul_public(s, 1 / 32, key=_k(11))
        assert not led.records                # no wire, no dealer
        assert z.fb == RING64.frac_bits + 5
        assert np.allclose(np.asarray(reveal(z)), [0.0625, -0.09375])

    def test_negative_pow2_folds_with_negation(self, x64):
        s = share(_k(12), jnp.asarray([2.0, -3.0]), RING64)
        z = mops.mul_public(s, -0.5, key=_k(13))
        assert z.fb == RING64.frac_bits + 1
        assert np.allclose(np.asarray(reveal(z)), [-1.0, 1.5])

    def test_negative_general_scalar(self, x64):
        s = share(_k(14), jnp.asarray([2.0, -3.0]), RING64)
        z = mops.mul_public(s, -1.5, key=_k(15))
        assert z.fb == 2 * RING64.frac_bits   # encoded at f, emitted 2f
        assert np.allclose(np.asarray(reveal(z)), [-3.0, 4.5], atol=1e-3)

    def test_general_scalar_on_deferred_input_forces_once(self):
        s = share(_k(16), jnp.asarray([1.0, 2.0]), RING32)
        z = mops.mul(s, s, _k(17))            # 2f
        with ledger_scope() as led:
            out = mops.mul_public(z, 1.5, key=_k(18))
        assert [r.op for r in led.records] == ["offline.trunc_pair",
                                               "trunc_open"]
        assert out.fb == 2 * RING32.frac_bits
        assert np.allclose(np.asarray(reveal(out)), [1.5, 6.0], atol=1e-2)


class TestReductions:
    """mops.mean under the reduction rule: at hidden 768 on RING32 a
    2f input forces down before the sum (keyed, one dealer pair), and
    1/768 is a free 2**-8 fold times an encoded 1/3."""

    D = 768

    def _x(self, i, shape=(2, 4, D)):
        return 0.55 * jax.random.normal(_k(i), shape)

    def test_mean_of_products_holds_at_hidden_768(self):
        x = self._x(80)
        xs = share(_k(81), x, RING32)
        sq = mops.mul(xs, xs, _k(82))                      # 2f
        mops.reset_reduce_stats()
        with ledger_scope() as led:
            var = mops.mean(sq, axis=-1, key=_k(83))
        assert mops.reduce_stats() == {"presum": 1, "odd_recip": 1}
        # the pre-sum pair (r, r >> shift) on every element, then the
        # folded sum's pair on every row
        n, rows = int(np.prod(x.shape)), int(np.prod(x.shape[:-1]))
        assert [(r.op, r.numel) for r in led.records] == [
            ("offline.trunc_pair", 2 * n), ("trunc_open", n),
            ("offline.trunc_pair", 2 * rows), ("trunc_open", rows)]
        want = np.asarray((x * x).mean(-1))
        assert np.abs(np.asarray(reveal(var)) - want).max() < 2e-3

    def _large_2f(self, i, n, level):
        """A 2f share of rows of n terms averaging `level`: a residual
        stream that attention outputs have grown (the pool's input)."""
        x = level + jax.random.normal(_k(i), (2, 3, n))
        xs = share(_k(i + 1), x, RING32)
        one = share(_k(i + 2), jnp.ones_like(x), RING32)
        return x, mops.mul(xs, one, _k(i + 3))              # 2f

    @pytest.mark.parametrize("n", [128, 768])
    @pytest.mark.parametrize("level", [15.0, 50.0])
    def test_mean_holds_terms_up_to_the_lattice_bound(self, n, level):
        """Terms averaging up to 2**SUM_ALLOWANCE = 64, the most a 2f
        product holds where a dealer truncation consumes it on RING32,
        sum without a wrap. Error: 2**-12-scale
        truncation noise plus 1/m's relative encoding error, at most
        m * 2**-(f+1) (m = 3 at n = 768)."""
        x, x2 = self._large_2f(100, n, level)
        got = np.asarray(reveal(mops.mean(x2, axis=-1, key=_k(104))))
        want = np.asarray(x.mean(-1))
        _, m = scale.split_reciprocal(n)
        tol = 2e-3 + m * 2.0 ** -(RING32.frac_bits + 1) * np.abs(want)
        assert (np.abs(got - want) <= tol).all(), np.abs(got - want).max()

    def test_old_allowance_wraps_a_grown_pool(self, monkeypatch):
        """With the rule's first allowance (terms below 4 against the whole
        ring, the shifts of SUM_ALLOWANCE 1 against the truncation's
        range) the pool of a stream averaging 15 over 128 positions
        wraps."""
        monkeypatch.setattr(scale, "SUM_ALLOWANCE", 1)
        x, x2 = self._large_2f(105, 128, 15.0)
        got = np.asarray(reveal(mops.mean(x2, axis=-1, key=_k(109))))
        assert np.abs(got - np.asarray(x.mean(-1))).min() > 1.0

    def test_canonical_input_is_never_forced(self):
        x = self._x(84)
        xs = share(_k(85), x, RING32)
        mops.reset_reduce_stats()
        mu = mops.mean(xs, axis=-1, key=_k(86))
        assert mops.reduce_stats() == {"presum": 0, "odd_recip": 1}
        assert np.abs(np.asarray(reveal(mu))
                      - np.asarray(x.mean(-1))).max() < 1e-3

    def test_pow2_mean_stays_a_free_fold(self):
        x = self._x(87, (2, 4, 256))
        xs = share(_k(88), x, RING32)
        mops.reset_reduce_stats()
        with ledger_scope() as led:
            mu = mops.mean(xs, axis=-1, key=_k(89))
        assert led.records == [] and mu.fb == RING32.frac_bits + 8
        assert mops.reduce_stats() == {"presum": 0, "odd_recip": 0}

    def test_ring64_needs_no_presum(self, x64):
        x = self._x(90)
        xs = share(_k(91), x)
        sq = mops.mul(xs, xs, _k(92))
        mops.reset_reduce_stats()
        var = mops.mean(sq, axis=-1, key=_k(93))
        assert mops.reduce_stats()["presum"] == 0
        assert np.abs(np.asarray(reveal(var))
                      - np.asarray((x * x).mean(-1))).max() < 1e-4


# ---------------------------------------------------------------------------
# 4. the forced-trunc guard
# ---------------------------------------------------------------------------

class TestForcedGuard:
    def test_double_mul_chain_fires_guard_on_ring32(self):
        """f -> 2f -> 3f would overflow the 32-bit ring (3f = 36 bits):
        the headroom plan forces the 2f operand back to canonical with
        a REAL dealer trunc, and the product lands correct at 2f."""
        vals = jnp.asarray([3.0, -2.5, 1.25])
        x = share(_k(20), vals, RING32)
        y = share(_k(21), vals, RING32)
        z = share(_k(22), vals, RING32)
        a = mops.mul(x, y, _k(23))
        assert a.excess == RING32.frac_bits
        with ledger_scope() as led:
            b = mops.mul(a, z, _k(24))
        trunc_ops = [r.op for r in led.records if "trunc" in r.op]
        assert trunc_ops == ["offline.trunc_pair", "trunc_open"], \
            "the forced-trunc guard must fire exactly once"
        assert b.fb == 2 * RING32.frac_bits
        want = np.asarray(vals) ** 3
        assert np.allclose(np.asarray(reveal(b)), want, atol=2e-2)

    def test_square_of_deferred_value_truncs_once(self):
        s = share(_k(25), jnp.asarray([1.5, 0.5]), RING32)
        z = mops.mul(s, s, _k(26))
        with ledger_scope() as led:
            z2 = mops.mul(z, z, _k(27))       # (2f, 2f) same object
        assert sum(1 for r in led.records if r.op == "trunc_open") == 1
        assert np.allclose(np.asarray(reveal(z2)),
                           np.asarray([1.5, 0.5]) ** 4, atol=2e-2)

    def test_cap_is_ring_parameterized(self):
        """RING64 has sign + headroom for a third fraction (3f = 48 <
        63); RING32 does not (3f = 36 > 31); no bit width means the
        conservative 2f contract."""
        assert scale.cap(RING64.frac_bits, RING64.bits) == \
            3 * RING64.frac_bits
        assert scale.cap(RING32.frac_bits, RING32.bits) == \
            2 * RING32.frac_bits
        assert scale.cap(16) == 32

    def test_double_mul_chain_defers_on_ring64(self, x64):
        """The exact chain that forces a dealer trunc on RING32 rides
        to 3f force-free under the RING64 headroom cap — but ONLY on a
        backend whose truncation is exact at any exponent (aby3trunc
        trunc2 here; spdz2pc's MAC'd pairs likewise). This is the
        ring-cap dividend bench_fusion tracks as
        ring64_trunc_event_delta."""
        vals = jnp.asarray([3.0, -2.5, 1.25])
        x = share(_k(40), vals, RING64, "aby3trunc")
        y = share(_k(41), vals, RING64, "aby3trunc")
        z = share(_k(42), vals, RING64, "aby3trunc")
        a = mops.mul(x, y, _k(43))
        assert a.excess == RING64.frac_bits
        with ledger_scope() as led:
            b = mops.mul(a, z, _k(44))
        assert not [r.op for r in led.records if "trunc" in r.op], \
            "3f fits RING64 headroom: no forced truncation"
        assert b.fb == 3 * RING64.frac_bits
        want = np.asarray(vals) ** 3
        assert np.allclose(np.asarray(reveal(b)), want, atol=2e-2)
        # the exactness guard: default 2pc's RING64 truncation is a
        # probabilistic local shift (wrap prob ~ encoded/2**63 — 2**16x
        # worse at 3f), so the lattice denies it the deferral: the same
        # chain forces back under the 2f cap and stays correct
        x2, y2, z2 = (share(_k(45 + i), vals, RING64) for i in range(3))
        b2 = mops.mul(mops.mul(x2, y2, _k(48)), z2, _k(49))
        assert b2.fb == 2 * RING64.frac_bits
        assert np.allclose(np.asarray(reveal(b2)), want, atol=2e-2)

    def test_force_memo_spans_consumers(self):
        """Two independent consumers of one deferred tensor pay ONE
        truncation (the ops.force cache) — the event reduction the
        acceptance gate counts."""
        s = share(_k(28), jnp.asarray([1.0, -1.0, 2.0]), RING32)
        z = mops.mul(s, s, _k(29))
        w = share(_k(30), jnp.asarray([0.5, 0.5, 0.5]), RING32)
        with ledger_scope() as led:
            mops.mul(z, w, _k(31))
            mops.mul(z, w, _k(32))
        assert sum(1 for r in led.records if r.op == "trunc_open") == 1

    def test_broadcast_force_bills_preblast_numel(self):
        """Lineage: forcing a broadcast truncates the SOURCE (n elems),
        not the broadcast (n * rows) — fewer dealer pair bytes for the
        same event."""
        eng = MPCEngine(RING32).with_key(_k(33))
        s = share(_k(34), jnp.asarray([1.0, 2.0]), RING32)
        z = mops.mul(s, s, _k(35))            # (2,) at 2f
        zb = eng.broadcast(eng.reshape(z, (1, 2)), (64, 2))
        with ledger_scope() as led:
            mops.force(zb, _k(36))
        (pair, opn) = led.records
        assert (pair.op, pair.numel) == ("offline.trunc_pair", 4)
        assert (opn.op, opn.numel) == ("trunc_open", 2)   # NOT 128

    def test_relu_is_truncation_free_on_deferred_input(self):
        """Comparison bits share at exponent 0: ReLU of a 2f tensor
        records a comparison and a multiply — no truncation anywhere —
        and keeps the carried exponent."""
        s = share(_k(37), jnp.asarray([1.5, -0.5, 2.0]), RING32)
        z = mops.mul(s, s, _k(38))
        with ledger_scope() as led:
            r = compare.relu(z, _k(39))
        assert not any("trunc" in rec.op for rec in led.records)
        assert r.fb == z.fb
        assert np.allclose(np.asarray(reveal(r)),
                           np.maximum(np.asarray([1.5, -0.5, 2.0]) ** 2, 0),
                           atol=2e-2)

    def test_3pc_force_prices_rereplication_bytes(self, x64):
        """The PR 4 follow-up: a keyed 3PC truncation is no longer free
        — one output component rides the resharing flight (0 rounds)."""
        s = share(_k(40), jnp.asarray([1.0, 2.0, 3.0]), RING64, "3pc")
        z = mops.mul(s, s, _k(41))
        with ledger_scope() as led:
            mops.force(z, _k(42))
        (rec,) = led.records
        assert (rec.op, rec.rounds, rec.tag) == ("trunc_reshare", 0, "bw")
        assert rec.nbytes == RING64.elem_bytes * 3
        assert led.offline_nbytes == 0        # still dealer-free


# ---------------------------------------------------------------------------
# 4b. multi-layer RING32: the above-cap align-down must be a KEYED trunc
# ---------------------------------------------------------------------------

class TestMultiLayerRing32:
    """Layer >= 2 is where the 2f residual meets a pow2-folded mean
    above the cap: the centering sub must down-trunc the mean with the
    dealer (exact), never a keyless local shift whose share-wrap
    probability at fb > 2f corrupts rows silently. Pinned by parity
    here AND by the two-layer probe streams of tests/test_probe_streams.py
    (the align-down is a real trunc event on the wire)."""

    L = 2

    def _setup(self):
        import dataclasses
        from repro.configs.paper_targets import TINY_TARGET
        from repro.core import proxy as proxy_mod
        from repro.core.proxy import ProxySpec
        cfg = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=2,
                                  d_model=32, n_heads=2, n_kv_heads=2,
                                  d_head=16, d_ff=64)
        spec = ProxySpec(self.L, 2, 4)
        pp = proxy_mod.random_proxy(_k(60), cfg, spec, seq_len=8,
                                    n_classes=3)
        return cfg, spec, pp

    def test_two_layer_ring32_parity(self):
        from repro.core import proxy as proxy_mod
        from repro.engine import ClearEngine, proxy_entropy
        cfg, spec, pp = self._setup()
        tok = jnp.asarray(np.random.default_rng(8).integers(
            0, cfg.vocab_size, (32, 8)))
        clear = np.asarray(proxy_entropy(ClearEngine(), pp, cfg, tok, spec))
        pp_sh = proxy_mod.share_proxy(_k(61), pp, RING32)
        x = jnp.take(pp["embed"], tok, axis=0) * (cfg.d_model ** 0.5)
        x_sh = share(_k(62), x.astype(jnp.float32), RING32)
        eng = MPCEngine(RING32).with_key(_k(63))
        got = np.asarray(reveal(proxy_entropy(eng, pp_sh, cfg, x_sh, spec)))
        # every row, not just the max: wrap corruption is row-sparse
        assert np.abs(got - clear).max() < 5e-3, np.abs(got - clear).max()


# ---------------------------------------------------------------------------
# 5. quickselect under scale-carrying scores
# ---------------------------------------------------------------------------

class TestQuickselectScale:
    N, TOPK = 48, 16

    @pytest.fixture
    def canonical(self, x64):
        vals = jnp.asarray(np.random.default_rng(5).normal(size=self.N),
                           jnp.float32)
        return share(_k(50), vals)

    def test_deferred_scores_select_same_set(self, canonical, x64):
        """`lift` is value-preserving, so the top-k of the 2f-scale pool
        must equal the canonical run's — comparisons force first."""
        deferred = mops.lift(canonical, RING64.frac_bits)
        assert deferred.excess == RING64.frac_bits
        base = quickselect.top_k_indices(canonical, self.TOPK, seed=3)
        got = quickselect.top_k_indices(deferred, self.TOPK, seed=3)
        assert np.array_equal(base, got)

    @pytest.mark.parametrize("wave", [1, 4])
    def test_per_wave_comparison_ledger_pinned(self, canonical, wave, x64):
        """Regression pin: after the entry force, every per-wave
        reveal_lt batch records EXACTLY the canonical run's flights —
        bitwise ledger agreement per wave (RING64 entry force is a free
        local shift, so the streams are identical end to end)."""
        deferred = mops.lift(canonical, RING64.frac_bits)
        with ledger_scope() as led_c:
            quickselect.top_k_indices(canonical, self.TOPK, seed=3,
                                      wave=wave)
        with ledger_scope() as led_d:
            quickselect.top_k_indices(deferred, self.TOPK, seed=3,
                                      wave=wave)
        recs_c = [(r.op, r.rounds, r.nbytes, r.numel, r.tag)
                  for r in led_c.records]
        recs_d = [(r.op, r.rounds, r.nbytes, r.numel, r.tag)
                  for r in led_d.records]
        assert recs_c == recs_d

    def test_entry_force_restores_canonical_compare_encoding(self, x64):
        """reveal_lt consumes canonical encodings: the pool is forced
        once up front, not per comparison batch."""
        vals = jnp.asarray([0.5, -1.0, 2.0, 1.0])
        deferred = mops.lift(share(_k(51), vals), RING64.frac_bits)
        idx = quickselect.top_k_indices(deferred, 2, seed=0)
        assert np.array_equal(idx, np.asarray([2, 3]))
