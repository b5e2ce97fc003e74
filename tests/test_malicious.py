"""Malicious-security tier: MAC'd 2PC shares + ABY3 exact truncation.

Contracts:
  1. MAC PLUMBING — honest partial opens under a `mac_scope` verify
     cleanly (n_opened > 0); flipping ONE bit in either a value
     component or a MAC component of any opened tensor makes the
     batched boundary check abort with `MacCheckError`.
  2. ABORT AT THE BOUNDARY — a full spdz2pc proxy forward with a
     tampered opening aborts at `MPCEngine.entropy_head` (which runs
     the constant-size `mac_check_flight`); the honest forward passes.
  3. PRICING — authenticated mul = MAC'd triple + sacrificed triple
     (offline) + sacrifice flight + beaver open (online); spdz2pc
     truncation pays a dealer MAC'd pair + opening round on BOTH rings
     (local shift is not MAC-preserving); the MAC check itself is
     constant-size.
  4. FORWARD PARITY — all six nonlinearity variants match ClearEngine
     on RING64 under spdz2pc AND aby3trunc, within the same per-variant
     tolerances the semi-honest 2PC path holds.
  5. PROBE + EXECUTION — the probe shows the overhead over each
     semi-honest baseline, and an executed WaveExecutor phase
     reproduces its pinned per-batch stream
     (tests/data/probe_streams.txt), passes ledger_agrees, and has the
     right party axis and clear-match scores.
  6. WRAP STATISTICS (slow) — replicated3pc probabilistic truncation
     measurably wraps at RING32 on large-magnitude values, at a rate
     consistent with the analytic |enc|/2^32 bound; aby3trunc's trunc2
     produces ZERO wraps on the same value stream.
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
from repro.engine import ClearEngine, MPCEngine, VARIANTS, cached_probe, \
    proxy_entropy
from repro.mpc import ops as mops, protocols
from repro.mpc.comm import ledger_scope
from repro.mpc.protocols.spdz2pc import (MacCheckError, mac_key, mac_scope,
                                         tamper_scope)
from repro.mpc.ring import RING32, RING64, x64_scope
from repro.mpc.sharing import reveal, share

from data import probe_streams as streams

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, CLASSES = 8, 6, 3
K = jax.random.key(0)

# the same per-variant tolerances the semi-honest paths hold
ATOL = {"full": 2e-3, "no-sm": 2e-2, "no-ln": 2e-2, "no-se": 6e-2,
        "quad_sm": 2e-2, "poly_sm": 2e-2}

RINGS = {"ring64": RING64, "ring32": RING32}
MALICIOUS = ("spdz2pc", "aby3trunc")
PARTIES = {"spdz2pc": 4, "aby3trunc": 3}   # share rows (spdz: 2 + 2 MAC)


def _k(i):
    return jax.random.fold_in(K, i)


# ---------------------------------------------------------------------------
# 1. MAC plumbing: honest pass, tampered abort
# ---------------------------------------------------------------------------

class TestMacCheck:
    def test_registry_and_layout(self, x64):
        be = protocols.get("spdz2pc")
        assert be.n_parties == 4
        s = share(_k(0), jnp.array([1.5, -2.25]), RING64, "spdz2pc")
        assert s.sh.shape == (4, 2)
        alpha, _, _ = mac_key(RING64)
        # rows 0+1 reconstruct x; rows 2+3 reconstruct alpha * x
        enc = np.asarray(s.sh[0] + s.sh[1])
        mac = np.asarray(s.sh[2] + s.sh[3])
        assert np.array_equal(mac, np.asarray(alpha) * enc)

    def test_honest_opens_verify(self, x64):
        with mac_scope() as st:
            got = np.asarray(reveal(share(_k(1), jnp.array([3.0, -1.25]),
                                          RING64, "spdz2pc")))
            assert st.n_opened > 0
            st.verify()                      # no abort
        assert np.allclose(got, [3.0, -1.25], atol=1e-3)

    @pytest.mark.parametrize("row", [0, 2], ids=["value-row", "mac-row"])
    def test_single_bit_flip_aborts(self, row, x64):
        """Flip one bit in a value component (row 0) or a MAC component
        (row 2) of the opened tensor: the batched check must abort."""
        x = share(_k(2), jnp.array([1.0, 2.0, 3.0]), RING64, "spdz2pc")
        with mac_scope() as st:
            with tamper_scope(lambda sh: sh.at[row, 1].add(1 << 3)):
                reveal(x)
            with pytest.raises(MacCheckError, match="tampered"):
                st.verify()

    def test_tampered_mul_opening_aborts(self, x64):
        """The adversary corrupts a Beaver (eps, delta) opening instead
        of a final output — still caught: every partial open carries an
        obligation."""
        x = share(_k(3), jnp.ones((4,)), RING64, "spdz2pc")
        with mac_scope() as st:
            with tamper_scope(lambda sh: sh.at[1, 0].add(1)):
                mops.force(mops.mul(x, x, _k(4)), _k(5))
            assert st.n_opened > 0
            with pytest.raises(MacCheckError):
                st.verify()

    def test_honest_mul_chain_verifies(self, x64):
        x = share(_k(6), jnp.array([0.5, -1.5]), RING64, "spdz2pc")
        with mac_scope() as st:
            z = mops.force(mops.mul(x, x, _k(7)), _k(8))
            got = np.asarray(reveal(z))
            assert st.n_opened >= 3          # sacrifice? beaver, trunc, open
            st.verify()
        assert np.allclose(got, [0.25, 2.25], atol=1e-3)

    def test_trunc_requires_key(self, x64):
        x = share(_k(9), jnp.ones((2,)), RING64, "spdz2pc")
        with pytest.raises(ValueError, match="MAC-preserving"):
            protocols.get("spdz2pc").trunc(x, None)


# ---------------------------------------------------------------------------
# 2. the tampered FORWARD aborts at the engine boundary
# ---------------------------------------------------------------------------

class TestForwardAbort:
    def _forward(self, pp, tok):
        pp_sh = proxy_mod.share_proxy(_k(10), pp, RING64, "spdz2pc")
        x = jnp.take(pp["embed"], tok, axis=0) * (CFG.d_model ** 0.5)
        x_sh = share(_k(11), x.astype(jnp.float32), RING64, "spdz2pc")
        eng = MPCEngine(protocol="spdz2pc").with_key(_k(12))
        return proxy_entropy(eng, pp_sh, CFG, x_sh, SPEC, VARIANTS["full"])

    def test_honest_forward_passes_boundary_check(self, pp, tok, x64):
        with mac_scope() as st:
            ent = self._forward(pp, tok)     # entropy_head verifies
            assert st.n_opened > 0
        assert ent.sh.shape[0] == 4

    def test_tampered_forward_aborts_at_entropy_head(self, pp, tok, x64):
        """One flipped bit anywhere in the forward's many partial opens
        is caught by the ONE constant-size check at the output."""
        with mac_scope():
            with tamper_scope(lambda sh: sh.at[0, 0].add(1 << 5)):
                with pytest.raises(MacCheckError, match="aborting"):
                    self._forward(pp, tok)


# ---------------------------------------------------------------------------
# 3. pricing: sacrifice, MAC'd dealer bytes, trunc on BOTH rings
# ---------------------------------------------------------------------------

class TestMaliciousPricing:
    def test_mul_records_sacrifice_and_doubled_triples(self, x64):
        n = 6
        x = share(_k(20), jnp.ones((n,)), RING64, "spdz2pc")
        with ledger_scope() as led:
            mops.mul(x, x, _k(21))
        assert [r.op for r in led.records] == \
            ["offline.mul_triple", "offline.sacrifice_triple",
             "sacrifice", "beaver_mul"]
        assert [r.tag for r in led.records] == \
            ["offline", "offline", "bw", "bw"]
        eb = RING64.elem_bytes
        # MAC'd triples are 4 components/value; sacrifice doubles them
        assert led.offline_nbytes == 2 * (4 * eb * 3 * n)
        # online wire stays semi-honest-sized: value components only
        assert led.records[2].nbytes == led.records[3].nbytes == 4 * eb * n
        assert led.rounds == 2               # sacrifice + beaver open

    @pytest.mark.parametrize("ring", list(RINGS.values()), ids=list(RINGS))
    def test_trunc_pays_dealer_pair_on_both_rings(self, ring, x64):
        """Semi-honest RING64 truncation is free (local shift); the
        MAC'd tier pays a dealer pair + opening round on EVERY ring —
        the malicious overhead curve's RING64 story."""
        x = share(_k(22), jnp.ones((5,)), ring, "spdz2pc")
        p = mops.mul(x, x, _k(23))
        with ledger_scope() as led:
            mops.force(p, _k(24))
        assert [r.op for r in led.records] == \
            ["offline.trunc_pair", "trunc_open"]
        assert led.rounds == 1
        assert led.offline_nbytes == 4 * ring.elem_bytes * 2 * 5
        # semi-honest 2pc at RING64: same force is ledger-silent
        if ring is RING64:
            q = mops.mul(share(_k(25), jnp.ones((5,)), ring, "2pc"),
                         share(_k(26), jnp.ones((5,)), ring, "2pc"),
                         _k(27))
            with ledger_scope() as led2:
                mops.force(q, _k(28))
            assert led2.records == []

    def test_mac_check_flight_is_constant_size(self, x64):
        be = protocols.get("spdz2pc")
        with ledger_scope() as led:
            be.mac_check_flight(RING64)
        assert [r.op for r in led.records] == ["offline.mac_key",
                                               "mac_check"]
        assert led.rounds == 1
        assert led.nbytes == 4 * RING64.elem_bytes        # one combination
        assert led.offline_nbytes == 2 * RING64.elem_bytes

    def test_aby3_trunc2_two_rounds_no_dealer(self, x64):
        x = share(_k(29), jnp.ones((5,)), RING32, "aby3trunc")
        p = mops.mul(x, x, _k(30))
        with ledger_scope() as led:
            mops.force(p, _k(31))
        (rec,) = led.records
        assert rec.op == "trunc2" and rec.rounds == 2
        assert rec.nbytes == 6 * RING32.elem_bytes * 5
        assert led.offline_nbytes == 0


# ---------------------------------------------------------------------------
# 4. full-forward parity: all six variants, both malicious backends
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pp():
    return proxy_mod.random_proxy(K, CFG, SPEC, seq_len=SEQ,
                                  n_classes=CLASSES)


@pytest.fixture(scope="module")
def tok():
    return jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (BATCH, SEQ)))


class TestMaliciousParity:
    @pytest.mark.parametrize("proto", MALICIOUS)
    @pytest.mark.parametrize("vname", sorted(VARIANTS))
    def test_variant_parity_ring64(self, vname, proto, pp, tok, x64):
        """Acceptance bar: hardening the protocol must not move the
        numbers — both malicious-tier backends match ClearEngine within
        the SEMI-HONEST tolerances on every variant strategy."""
        variant = VARIANTS[vname]
        clear = np.asarray(proxy_entropy(ClearEngine(), pp, CFG, tok,
                                         SPEC, variant))
        pp_sh = proxy_mod.share_proxy(_k(30), pp, RING64, proto)
        x = jnp.take(pp["embed"], tok, axis=0) * (CFG.d_model ** 0.5)
        x_sh = share(_k(31), x.astype(jnp.float32), RING64, proto)
        eng = MPCEngine(protocol=proto).with_key(_k(32))
        got = np.asarray(reveal(proxy_entropy(eng, pp_sh, CFG, x_sh,
                                              SPEC, variant)))
        err = np.abs(got - clear).max()
        assert err < ATOL[vname], (proto, vname, err)


# ---------------------------------------------------------------------------
# 5. the probe + executed malicious phases
# ---------------------------------------------------------------------------

class TestMaliciousMirror:
    def test_overhead_shape(self):
        """The curve bench_fusion emits, asserted at its source: spdz2pc
        pays rounds (trunc no longer free) and dealer bytes over 2pc;
        aby3trunc pays trunc2 rounds over 3pc but stays dealer-free."""
        kw = dict(batch=BATCH, seq=SEQ, classes=CLASSES)
        base2 = cached_probe(CFG, SPEC, **kw, ring=RING64, protocol="2pc")
        mal2 = cached_probe(CFG, SPEC, **kw, ring=RING64,
                            protocol="spdz2pc")
        assert mal2.rounds > base2.rounds
        assert mal2.offline_nbytes > base2.offline_nbytes
        base3 = cached_probe(CFG, SPEC, **kw, ring=RING32, protocol="3pc")
        mal3 = cached_probe(CFG, SPEC, **kw, ring=RING32,
                            protocol="aby3trunc")
        assert mal3.rounds > base3.rounds
        assert mal3.offline_nbytes == base3.offline_nbytes == 0


class TestExecutedMaliciousPhase:
    POOL = 24

    @pytest.fixture(scope="class", params=MALICIOUS)
    def executed(self, request, pp):
        proto = request.param
        pool = np.random.default_rng(0).integers(0, CFG.vocab_size,
                                                 (self.POOL, SEQ))
        out = {"proto": proto}
        for name, fuse in (("eager", False), ("fused", True)):
            ex = WaveExecutor(ExecConfig(wave=2, batch=8, ring=RING64,
                                         protocol=proto, fuse=fuse))
            ent = ex.score_phase(_k(40), pp, CFG, pool, SPEC)
            out[name] = (np.asarray(ent.sh), ex.reports[-1])
        return out

    def test_ledger_agrees(self, executed):
        for name in ("eager", "fused"):
            rep = executed[name][1]
            assert rep.agrees(), (executed["proto"], name)

    def test_party_axis(self, executed):
        assert executed["fused"][0].shape[0] == PARTIES[executed["proto"]]

    def test_malicious_events_in_executed_ledger(self, executed):
        led = executed["eager"][1].ledger
        ops_ = [r.op for r in led.records]
        if executed["proto"] == "spdz2pc":
            assert any(o.endswith("mac_check") for o in ops_)
            assert any(o == "sacrifice" for o in ops_)
            assert led.offline_nbytes > 0
        else:
            assert any(o.endswith("trunc2") for o in ops_)
            assert led.offline_nbytes == 0

    def test_per_batch_matches_mirror(self, executed):
        for name in ("eager", "fused"):
            streams.assert_matches(
                streams.Case("tiny", executed["proto"], "ring64", name, 8),
                executed[name][1].per_batch)

    def test_scores_match_clear(self, executed, pp):
        pool = np.random.default_rng(0).integers(0, CFG.vocab_size,
                                                 (self.POOL, SEQ))
        clear = np.asarray(proxy_entropy(ClearEngine(), pp, CFG,
                                         jnp.asarray(pool), SPEC))
        be = protocols.get(executed["proto"])
        with x64_scope():
            sh = jnp.asarray(executed["fused"][0])
            got = np.asarray(be.reconstruct(sh).astype(jnp.float64)
                             / RING64.scale)
        assert np.abs(got - clear).max() < 1e-3
        assert np.array_equal(executed["eager"][0], executed["fused"][0])


# ---------------------------------------------------------------------------
# 6. wrap statistics: probabilistic vs exact truncation (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestWrapStatistics:
    N = 4096
    SHIFT = 6

    def _values(self):
        # large magnitudes: |enc| up to ~2.46e8 of RING32's 2^31 range,
        # i.e. per-element wrap probability ~|enc|/2^32 up to ~6%
        rng = np.random.default_rng(7)
        return rng.uniform(-6e4, 6e4, self.N).astype(np.float32)

    def _trunc_err(self, proto):
        v = self._values()
        x = share(_k(50), jnp.asarray(v), RING32, proto)
        z = mops.trunc(x, key=_k(51), shift=self.SHIFT)
        assert z.fb == RING32.frac_bits - self.SHIFT
        return np.abs(np.asarray(reveal(z)) - v)

    def test_replicated_trunc_wraps_within_analytic_bound(self):
        """RING32 replicated-3pc probabilistic truncation on this value
        stream MUST wrap (error quantum 2^(32-f) per wrapped element),
        at a rate consistent with the analytic sum(|enc|)/2^32 bound."""
        err = self._trunc_err("3pc")
        wraps = int((err > 1e5).sum())       # quantum is 2^20 ~ 1.05e6
        expected = float(np.abs(self._values()
                                * RING32.scale).sum()) / 2.0 ** 32
        assert wraps > 0, "stream was chosen to wrap measurably"
        assert expected / 5 < wraps < expected * 5, (wraps, expected)
        # non-wrapped elements still meet the ulp bound at fb - shift
        fine = err[err <= 1e5]
        assert fine.max() < 4 * 2.0 ** -(RING32.frac_bits - self.SHIFT)

    def test_aby3_trunc2_zero_wraps_same_stream(self):
        """The exact scheme on the SAME values: no wraps, <= a couple
        ulp of the output exponent — the reason aby3trunc exists."""
        err = self._trunc_err("aby3trunc")
        assert int((err > 1e5).sum()) == 0
        assert err.max() < 4 * 2.0 ** -(RING32.frac_bits - self.SHIFT)
