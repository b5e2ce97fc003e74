"""Protocol backends: replicated 2-of-3 sharing kills the trusted dealer.

Contracts:
  1. BACKEND CORRECTNESS — replicated-3PC share/open/mul/matmul/trunc
     reconstruct the same values as the 2PC additive backend, with the
     scheme's own wire model (3 * elem_bytes opens, output-proportional
     resharing flights) and ZERO dealer events.
  2. OFFLINE CHANNEL — 2PC dealer bytes (Beaver triples, trunc pairs)
     land under tag="offline": excluded from Ledger.nbytes/makespan,
     reported via offline_nbytes, mirrored by the analytic formulas.
  3. FORWARD PARITY — a full RING64 3PC proxy forward matches
     ClearEngine within the same tolerance the 2PC path holds, for all
     six variant strategies.
  4. PROBE + EXECUTION — an executed 3PC phase reproduces its pinned
     per-batch stream (tests/data/probe_streams.txt) record for record
     and passes iosched.ledger_agrees with no offline/dealer event; the
     3pc RING32 probe pays no truncation rounds.
  5. SHAPE OPS ACROSS BACKENDS — broadcast with negative/padded axes,
     moveaxis/swapaxes/index with negative indices, and scalar shares
     agree with ClearEngine on BOTH backends (the PR 2 qkv_bias
     party-axis bug class, previously pinned only for 2PC).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_targets import TINY_TARGET
from repro.core import proxy as proxy_mod
from repro.core.executor import ExecConfig, WaveExecutor
from repro.core.proxy import ProxySpec
from repro.engine import (ClearEngine, MPCEngine, VARIANTS, cached_probe,
                          proxy_entropy, resolve_engine)
from repro.mpc import ops as mops, compare, protocols
from repro.mpc.comm import ledger_scope
from repro.mpc.ring import RING32, RING64, x64_scope
from repro.mpc.sharing import reveal, share

from data import probe_streams as streams

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, CLASSES = 8, 6, 3
K = jax.random.key(0)

# same per-variant tolerances the 2PC parity sweep holds (test_engine.py)
ATOL = {"full": 2e-3, "no-sm": 2e-2, "no-ln": 2e-2, "no-se": 6e-2,
        "quad_sm": 2e-2, "poly_sm": 2e-2}

RINGS = {"ring64": RING64, "ring32": RING32}
PROTOS = ("2pc", "3pc")


def _k(i):
    return jax.random.fold_in(K, i)


# ---------------------------------------------------------------------------
# 1. backend primitives
# ---------------------------------------------------------------------------

class TestReplicatedSharing:
    def test_registry(self):
        assert protocols.get("2pc").n_parties == 2
        assert protocols.get("3pc").n_parties == 3
        with pytest.raises(ValueError, match="unknown protocol"):
            protocols.get("4pc")

    def test_share_roundtrip_and_layout(self, x64):
        x = jnp.array([1.5, -2.25, 1000.0, -0.0001, 0.0])
        s = share(_k(0), x, RING64, "3pc")
        assert s.sh.shape == (3, 5) and s.n_parties == 3
        assert s.proto == "3pc"
        assert np.allclose(np.asarray(reveal(s)), x, atol=1e-3)

    def test_single_component_is_uniform(self, x64):
        """Any lone component must carry no information (2-of-3: one
        party's PAIR of components is two independent uniforms)."""
        x = jnp.full((4096,), 7.25)
        s = share(_k(1), x, RING64, "3pc")
        for i in range(3):
            comp = np.asarray(s.sh[i], dtype=np.float64)
            assert np.std(comp) > 2 ** 60, i

    def test_open_wire_model(self, x64):
        """open_ no longer hard-codes 2 * elem_bytes: bytes follow the
        backend's party count."""
        x = jnp.ones((10,))
        for proto, parties in (("2pc", 2), ("3pc", 3)):
            with ledger_scope() as led:
                reveal(share(_k(2), x, RING64, proto))
            (rec,) = led.records
            assert rec.nbytes == parties * RING64.elem_bytes * 10, proto

    @pytest.mark.parametrize("ring", list(RINGS.values()), ids=list(RINGS))
    def test_mul_matches_2pc_values(self, ring, x64):
        x = jnp.array([1.5, -2.0, 0.25, 3.0], jnp.float32)
        y = jnp.array([2.0, 1.5, -4.0, 0.5], jnp.float32)
        got = reveal(mops.mul(share(_k(3), x, ring, "3pc"),
                              share(_k(4), y, ring, "3pc"), _k(5)))
        assert np.allclose(np.asarray(got), x * y,
                           atol=8.0 / ring.scale * (1 + 8))

    def test_matmul_and_relu(self, x64):
        a = jax.random.normal(_k(6), (5, 7))
        b = jax.random.normal(_k(7), (7, 3))
        z = reveal(mops.matmul(share(_k(8), a, RING64, "3pc"),
                               share(_k(9), b, RING64, "3pc"), _k(10)))
        assert np.allclose(np.asarray(z), np.asarray(a @ b), atol=1e-3)
        x = jnp.array([-3.0, -0.5, 0.0, 0.5, 3.0])
        r = reveal(compare.relu(share(_k(11), x, RING64, "3pc"), _k(12)))
        assert np.allclose(np.asarray(r), np.maximum(x, 0), atol=1e-3)

    def test_public_ops_preserve_proto(self, x64):
        x = jnp.array([1.0, -2.0, 3.0])
        xs = share(_k(13), x, RING64, "3pc")
        for out, want in ((mops.add_public(xs, 2.5), x + 2.5),
                          (mops.mul_public(xs, -1.5), x * -1.5),
                          (mops.mul_public_int(xs, 3), x * 3),
                          (mops.neg(xs), -x)):
            assert out.proto == "3pc" and out.n_parties == 3
            assert np.allclose(np.asarray(reveal(out)), want, atol=1e-3)

    def test_mixed_protocol_inputs_rejected(self, x64):
        x2 = share(_k(14), jnp.ones((4,)), RING64, "2pc")
        eng = MPCEngine(protocol="3pc").with_key(_k(15))
        with pytest.raises(ValueError, match="protocol"):
            eng.embed(None, x2, CFG)


# ---------------------------------------------------------------------------
# 2. the offline dealer channel
# ---------------------------------------------------------------------------

class TestOfflineChannel:
    def test_2pc_mul_records_dealer_bytes(self, x64):
        """A scale-carrying mul records the triple + opening only; the
        dealer trunc pair arrives when a consumer FORCES the carried
        2f exponent (mpc/scale.py) — one pair per forced value."""
        x = share(_k(20), jnp.ones((6,)), RING32)
        y = share(_k(21), jnp.ones((6,)), RING32)
        with ledger_scope() as led:
            z = mops.mul(x, y, _k(22))
            assert z.excess == RING32.frac_bits     # rides at 2f
            zc = mops.force(z, _k(23))
            assert zc.excess == 0
            # the force memo: a second consumer pays nothing
            assert mops.force(z, _k(24)) is zc
        tags = [r.tag for r in led.records]
        assert tags == ["offline", "bw", "offline", "bw"]
        # triple: 3 tensors of 6 elems; trunc pair: 2 tensors of 6
        assert led.offline_nbytes == 2 * RING32.elem_bytes * (18 + 12)
        # offline bytes are NOT online wire bytes
        assert led.nbytes == sum(r.nbytes for r in led.records
                                 if r.tag == "bw")
        # and offline rounds are zero: the round count is online-only
        assert led.rounds == 2

    def test_3pc_has_zero_offline(self, x64):
        x = share(_k(23), jnp.ones((6,)), RING32, "3pc")
        with ledger_scope() as led:
            z = mops.mul(x, x, _k(24))
            mops.matmul(z.reshape(2, 3), share(_k(25), jnp.ones((3, 2)),
                                               RING32, "3pc"), _k(26))
        assert led.offline_nbytes == 0
        assert all(r.tag != "offline" for r in led.records)

    def test_triple_bytes_helper(self):
        from repro.mpc import beaver
        assert beaver.triple_bytes((4,), (4,), (4,), RING64) == \
            2 * 8 * 12


# ---------------------------------------------------------------------------
# 3. full-forward clear/MPC parity on the dealer-free backend
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pp():
    return proxy_mod.random_proxy(K, CFG, SPEC, seq_len=SEQ,
                                  n_classes=CLASSES)


@pytest.fixture(scope="module")
def tok():
    return jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (BATCH, SEQ)))


class Test3PCParity:
    @pytest.mark.parametrize("vname", sorted(VARIANTS))
    def test_variant_parity_ring64(self, vname, pp, tok, x64):
        """The acceptance bar: RING64 3PC matches ClearEngine within the
        tolerance the 2PC path holds, on every variant strategy."""
        variant = VARIANTS[vname]
        clear = np.asarray(proxy_entropy(ClearEngine(), pp, CFG, tok,
                                         SPEC, variant))
        pp_sh = proxy_mod.share_proxy(_k(30), pp, RING64, "3pc")
        x = jnp.take(pp["embed"], tok, axis=0) * (CFG.d_model ** 0.5)
        x_sh = share(_k(31), x.astype(jnp.float32), RING64, "3pc")
        eng = MPCEngine(protocol="3pc").with_key(_k(32))
        got = np.asarray(reveal(proxy_entropy(eng, pp_sh, CFG, x_sh,
                                              SPEC, variant)))
        err = np.abs(got - clear).max()
        assert err < ATOL[vname], (vname, err)


# ---------------------------------------------------------------------------
# 4. the probe + executed 3PC phase (the acceptance criterion)
# ---------------------------------------------------------------------------

class Test3PCMirror:
    def test_3pc_trunc_free_on_ring32(self):
        """The dealer's other product — trunc pairs — is gone too: the
        3pc RING32 stream has no trunc_open rounds, so it pays exactly
        the RING64 3pc round count."""
        kw = dict(batch=BATCH, seq=SEQ, classes=CLASSES)
        l32 = cached_probe(CFG, SPEC, **kw, ring=RING32, protocol="3pc")
        l64 = cached_probe(CFG, SPEC, **kw, ring=RING64, protocol="3pc")
        assert l32.rounds == l64.rounds
        two32 = cached_probe(CFG, SPEC, **kw, ring=RING32, protocol="2pc")
        assert two32.rounds > l32.rounds          # dealer truncs gone
        assert two32.offline_nbytes > 0 == l32.offline_nbytes


class TestExecuted3PCPhase:
    POOL = 24

    @pytest.fixture(scope="class")
    def executed(self, pp):
        pool = np.random.default_rng(0).integers(0, CFG.vocab_size,
                                                 (self.POOL, SEQ))
        out = {}
        for name, fuse in (("eager", False), ("fused", True)):
            ex = WaveExecutor(ExecConfig(wave=2, batch=8, ring=RING64,
                                         protocol="3pc", fuse=fuse))
            ent = ex.score_phase(_k(40), pp, CFG, pool, SPEC)
            out[name] = (np.asarray(ent.sh), ex.reports[-1])
        return out

    def test_ledger_agrees_and_no_dealer(self, executed):
        """Acceptance: an executed RING64 replicated-3PC phase passes
        ledger_agrees with ZERO dealer/offline events."""
        for name, (_, rep) in executed.items():
            assert rep.agrees(), name
            assert rep.ledger.offline_nbytes == 0, name
            bad = [r.op for r in rep.ledger.records
                   if r.tag == "offline" or r.op.startswith("offline")
                   or r.op.startswith("beaver")
                   or r.op.startswith("trunc_open")]
            assert not bad, (name, bad)

    def test_party_axis_is_three(self, executed):
        assert executed["fused"][0].shape[0] == 3

    def test_fusion_moves_flights_not_values(self, executed):
        assert np.array_equal(executed["eager"][0], executed["fused"][0])
        led_e = executed["eager"][1].ledger
        led_f = executed["fused"][1].ledger
        assert led_f.rounds < led_e.rounds
        assert led_f.nbytes == led_e.nbytes

    def test_per_batch_matches_mirror(self, executed):
        for name, (_, rep) in executed.items():
            streams.assert_matches(
                streams.Case("tiny", "3pc", "ring64", name, 8),
                rep.per_batch)

    def test_3pc_scores_match_clear(self, executed, pp):
        from repro.mpc.sharing import reconstruct
        pool = np.random.default_rng(0).integers(0, CFG.vocab_size,
                                                 (self.POOL, SEQ))
        clear = np.asarray(proxy_entropy(ClearEngine(), pp, CFG,
                                         jnp.asarray(pool), SPEC))
        with x64_scope():
            sh = jnp.asarray(executed["fused"][0])
            got = np.asarray(reconstruct(sh).astype(jnp.float64)
                             / RING64.scale)
        assert np.abs(got - clear).max() < 1e-3


# ---------------------------------------------------------------------------
# 5. share shape ops across backends (the qkv_bias bug class)
# ---------------------------------------------------------------------------

class TestShapeOpsAcrossBackends:
    """Every engine shape op vs the ClearEngine reference, on both
    protocol backends — negative axes, padded broadcasts, scalar
    shares. The party axis must never be confused with a value dim
    regardless of its size."""

    def _pair(self, proto, val, i=50):
        eng = MPCEngine(protocol=proto).with_key(_k(i))
        s = share(_k(i + 1), jnp.asarray(val, jnp.float32), RING64, proto)
        return eng, s

    @pytest.mark.parametrize("proto", PROTOS)
    def test_broadcast_padded_axes(self, proto, x64):
        ceng = ClearEngine()
        v = np.arange(4.0)
        eng, s = self._pair(proto, v)
        out = eng.broadcast(s, (3, 4))
        assert out.shape == (3, 4) and out.n_parties == eng.backend.n_parties
        want = ceng.broadcast(jnp.asarray(v), (3, 4))
        assert np.allclose(np.asarray(reveal(out)), np.asarray(want),
                           atol=1e-3)

    @pytest.mark.parametrize("proto", PROTOS)
    def test_broadcast_scalar_share(self, proto, x64):
        eng, s = self._pair(proto, 2.5, 52)
        out = eng.broadcast(s, (2, 3))
        assert out.shape == (2, 3)
        assert np.allclose(np.asarray(reveal(out)), 2.5, atol=1e-3)

    @pytest.mark.parametrize("proto", PROTOS)
    def test_moveaxis_swapaxes_negative(self, proto, x64):
        ceng = ClearEngine()
        v = np.random.default_rng(3).normal(size=(2, 3, 4))
        eng, s = self._pair(proto, v, 54)
        for fn, args in (("moveaxis", (-1, 0)), ("moveaxis", (1, -1)),
                         ("swapaxes", (-1, -2)), ("swapaxes", (0, -1))):
            got = getattr(eng, fn)(s, *args)
            want = getattr(ceng, fn)(jnp.asarray(v), *args)
            assert got.shape == tuple(want.shape), (fn, args)
            assert np.allclose(np.asarray(reveal(got)), np.asarray(want),
                               atol=1e-3), (fn, args)

    @pytest.mark.parametrize("proto", PROTOS)
    def test_index_negative_and_getitem(self, proto, x64):
        v = np.random.default_rng(4).normal(size=(5, 3))
        eng, s = self._pair(proto, v, 56)
        for i in (0, 2, -1, -5):
            got = eng.index(s, i)
            assert got.shape == (3,)
            assert np.allclose(np.asarray(reveal(got)), v[i], atol=1e-3), i
        sub = s[1:4]
        assert sub.shape == (3, 3) and sub.proto == proto
        assert np.allclose(np.asarray(reveal(sub)), v[1:4], atol=1e-3)

    @pytest.mark.parametrize("proto", PROTOS)
    def test_reshape_and_sum_negative_axis(self, proto, x64):
        v = np.random.default_rng(5).normal(size=(4, 6))
        eng, s = self._pair(proto, v, 58)
        r = eng.reshape(s, (2, 2, 6))
        assert r.shape == (2, 2, 6)
        tot = mops.sum_(r, axis=-1)
        assert tot.shape == (2, 2)
        assert np.allclose(np.asarray(reveal(tot)),
                           v.reshape(2, 2, 6).sum(-1), atol=1e-3)

    @pytest.mark.parametrize("proto", PROTOS)
    def test_qkv_bias_broadcast_regression(self, proto, x64):
        """The PR 2 party-axis bug, now pinned for BOTH backends: a
        (P, n)-share broadcast to (rows, n) must right-align the value
        dims, not glue the party axis onto a value dim."""
        b = np.random.default_rng(6).normal(size=(8,))
        eng, s = self._pair(proto, b, 60)
        out = eng.broadcast(s, (6, 8))
        assert np.allclose(np.asarray(reveal(out)),
                           np.broadcast_to(b, (6, 8)), atol=1e-3)


# ---------------------------------------------------------------------------
# resolution plumbing
# ---------------------------------------------------------------------------

class TestProtocolResolution:
    def test_resolve_engine_protocol(self):
        eng = resolve_engine("mpc", ring=RING32, protocol="3pc")
        assert isinstance(eng, MPCEngine)
        assert eng.protocol == "3pc" and eng.backend.n_parties == 3
        tr = resolve_engine("trace", protocol="3pc")
        assert tr.protocol == "3pc"

    def test_selection_config_syncs_protocol(self):
        from repro.core.selection import SelectionConfig
        sel = SelectionConfig(phases=[SPEC], engine=MPCEngine(
            RING64, protocol="3pc"))
        assert sel.executor.protocol == "3pc"
        sel2 = SelectionConfig(phases=[SPEC], mode="mpc",
                               executor=ExecConfig(protocol="3pc"))
        assert sel2.engine.protocol == "3pc"

    def test_share_pytree_roundtrip(self, x64):
        s = share(_k(70), jnp.ones((2, 2)), RING64, "3pc")
        leaves, treedef = jax.tree.flatten(s)
        s2 = jax.tree.unflatten(treedef, leaves)
        assert s2.proto == "3pc" and s2.ring is RING64
        assert np.array_equal(np.asarray(s.sh), np.asarray(s2.sh))
