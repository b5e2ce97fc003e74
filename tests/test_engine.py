"""Engine API: one forward, many substrates (src/repro/engine/).

Contracts:
  1. PARITY BY CONSTRUCTION — for every Table-2/Table-3 variant
     (full / no-sm / no-ln / no-se / quad_sm / poly_sm) the decoded MPC
     entropies match the clear engine within fixed-point tolerance.
     The exact-op and baseline variants run *real* share-level
     protocols (CrypTen softmax/rsqrt/entropy, 2Quad, Bolt polynomial)
     — their first MPC execution in this repo.
  2. REMOVED SHIMS — the deprecated proxy_entropy_clear/_mpc and
     approx.mlp_apply/_mpc back-compat wrappers are gone: the engine
     API is the only entry point.
  3. TRACE — TraceEngine prices variants without materializing weights
     (abstract_shares); its pinned streams are checked in
     tests/test_probe_streams.py.
  4. RESOLUTION — legacy mode strings resolve to engine instances.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_targets import TINY_TARGET
from repro.core import proxy as proxy_mod
from repro.core.proxy import ProxySpec
from repro.engine import (ClearEngine, MPCEngine, TraceEngine, VARIANTS,
                          abstract_shares, proxy_entropy, resolve_engine)
from repro.engine.base import FULL_VARIANT, TensorEngine
from repro.mpc.ring import RING32, RING64
from repro.mpc.sharing import reveal, share

from data import probe_streams as streams

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, CLASSES = 8, 6, 3
K = jax.random.key(0)

# decoded-MPC vs clear tolerance per variant: MLP emulators accumulate
# only truncation LSBs; exact-op variants add the CrypTen iterative
# approximations' own error (NR reciprocal/rsqrt, limit-approx exp,
# Householder log)
ATOL = {"full": 2e-3, "no-sm": 2e-2, "no-ln": 2e-2, "no-se": 6e-2,
        "quad_sm": 2e-2, "poly_sm": 2e-2}


@pytest.fixture(scope="module")
def pp():
    return proxy_mod.random_proxy(K, CFG, SPEC, seq_len=SEQ,
                                  n_classes=CLASSES)


@pytest.fixture(scope="module")
def tok():
    return jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (BATCH, SEQ)))


# ---------------------------------------------------------------------------
# 1. clear/MPC parity across every variant
# ---------------------------------------------------------------------------


class TestParitySweep:
    @pytest.mark.parametrize("vname", sorted(VARIANTS))
    def test_variant_parity(self, vname, pp, tok, x64):
        variant = VARIANTS[vname]
        clear = np.asarray(proxy_entropy(ClearEngine(), pp, CFG, tok,
                                         SPEC, variant))
        pp_sh = proxy_mod.share_proxy(jax.random.fold_in(K, 2), pp)
        x = jnp.take(pp["embed"], tok, axis=0) * (CFG.d_model ** 0.5)
        x_sh = share(jax.random.fold_in(K, 3), x.astype(jnp.float32))
        eng = MPCEngine().with_key(jax.random.fold_in(K, 4))
        got = np.asarray(reveal(proxy_entropy(eng, pp_sh, CFG, x_sh,
                                              SPEC, variant)))
        err = np.abs(got - clear).max()
        assert err < ATOL[vname], (vname, err)

    def test_qkv_bias_parity(self, pp, tok, x64):
        """Biased-attention archs (qkv_bias=True) run over MPC through
        the same forward: the bias share broadcast right-aligns value
        dims under the party axis (regression: both hand-written
        forwards crashed here, so biased archs had never executed or
        been priced over MPC)."""
        kb = jax.random.fold_in(K, 40)
        dh, w = CFG.d_head, SPEC.n_heads
        wk = min(w, CFG.n_kv_heads)
        pp_b = dict(pp)
        pp_b["attn"] = dict(pp["attn"])
        L = SPEC.n_layers
        pp_b["attn"]["bq"] = 0.05 * jax.random.normal(kb, (L, w * dh))
        pp_b["attn"]["bk"] = 0.05 * jax.random.normal(
            jax.random.fold_in(kb, 1), (L, wk * dh))
        pp_b["attn"]["bv"] = 0.05 * jax.random.normal(
            jax.random.fold_in(kb, 2), (L, wk * dh))
        clear = np.asarray(proxy_entropy(ClearEngine(), pp_b, CFG, tok,
                                         SPEC))
        pp_sh = proxy_mod.share_proxy(jax.random.fold_in(K, 41), pp_b)
        x = jnp.take(pp_b["embed"], tok, axis=0) * (CFG.d_model ** 0.5)
        x_sh = share(jax.random.fold_in(K, 42), x.astype(jnp.float32))
        eng = MPCEngine().with_key(jax.random.fold_in(K, 43))
        got = np.asarray(reveal(proxy_entropy(eng, pp_sh, CFG, x_sh,
                                              SPEC)))
        assert np.abs(got - clear).max() < ATOL["full"]
        # and the biased arch is priceable: the probe emits the pinned
        # unbiased stream record for record (biases add no wire cost)
        led = TraceEngine(RING64).probe(pp_sh, CFG, SPEC,
                                        (BATCH, SEQ, CFG.d_model))
        streams.assert_matches(
            streams.Case("tiny", "2pc", "ring64", "eager", BATCH), led)

    def test_softmax_strategies_differ(self, pp):
        """The strategies are real: distinct softmax ops, distinct
        probabilities (exact softmax rows sum to 1; 2Quad and the MLP
        emulator don't reproduce it bitwise)."""
        eng = ClearEngine()
        scores = jnp.asarray(np.random.default_rng(2).normal(
            size=(4, SEQ)) * 0.3, jnp.float32)
        outs = {v: np.asarray(eng.attn_probs(pp, 0, scores, VARIANTS[v]))
                for v in ("full", "quad_sm", "poly_sm", "no-sm")}
        assert np.allclose(outs["no-sm"].sum(-1), 1.0, atol=1e-6)
        for v in ("full", "quad_sm", "poly_sm"):
            assert not np.allclose(outs[v], outs["no-sm"], atol=1e-4), v


# ---------------------------------------------------------------------------
# 2. the deprecated shims are gone (PR 2 left them; this PR removes them)
# ---------------------------------------------------------------------------


class TestShimsRemoved:
    def test_proxy_shims_removed(self):
        assert not hasattr(proxy_mod, "proxy_entropy_clear")
        assert not hasattr(proxy_mod, "proxy_entropy_mpc")
        assert not hasattr(proxy_mod, "proxy_logits_clear")

    def test_approx_shims_removed(self):
        from repro.core import approx
        assert not hasattr(approx, "mlp_apply")
        assert not hasattr(approx, "mlp_apply_mpc")


# ---------------------------------------------------------------------------
# 3. TraceEngine: prices variants, no weights needed
# ---------------------------------------------------------------------------


class TestTrace:
    def test_baseline_softmaxes_cost_more(self):
        """quad/poly baselines pay reciprocal/comparison protocols the
        MLP emulator avoids — visible in the probed stream."""
        pp_sh = abstract_shares(CFG, SPEC, SEQ, CLASSES, RING64)
        led = {v: TraceEngine(RING64, VARIANTS[v]).probe(
                   pp_sh, CFG, SPEC, (BATCH, SEQ, CFG.d_model))
               for v in ("full", "quad_sm", "poly_sm")}
        assert led["quad_sm"].rounds > led["full"].rounds
        assert led["poly_sm"].rounds > led["quad_sm"].rounds


# ---------------------------------------------------------------------------
# 4. engine resolution + protocol surface
# ---------------------------------------------------------------------------


class TestResolution:
    def test_mode_strings(self):
        assert isinstance(resolve_engine("clear"), ClearEngine)
        eng = resolve_engine("mpc", ring=RING32)
        assert isinstance(eng, MPCEngine) and eng.ring is RING32
        assert isinstance(resolve_engine("trace"), TraceEngine)
        with pytest.raises(ValueError):
            resolve_engine("homomorphic")

    def test_instances_pass_through(self):
        eng = MPCEngine(ring=RING32)
        assert resolve_engine(eng) is eng

    def test_engines_satisfy_protocol(self):
        assert isinstance(ClearEngine(), TensorEngine)
        assert isinstance(MPCEngine(), TensorEngine)

    def test_unseeded_mpc_engine_refuses_keyed_ops(self):
        from repro.mpc.sharing import from_public
        x = from_public(jnp.ones((2, 2)), RING32)
        with pytest.raises(ValueError, match="with_key"):
            MPCEngine(RING32).mul(x, x)

    def test_selection_config_accepts_engine_and_string(self):
        from repro.core.executor import ExecConfig
        from repro.core.selection import SelectionConfig
        sel = SelectionConfig(phases=[SPEC], mode="mpc",
                              executor=ExecConfig(ring=RING32))
        assert isinstance(sel.engine, MPCEngine)
        assert sel.engine.ring is RING32
        sel2 = SelectionConfig(phases=[SPEC], engine=MPCEngine(RING32))
        assert sel2.mode == "mpc" and sel2.executor.ring is RING32
        assert SelectionConfig(phases=[SPEC]).mode == "clear"
        assert FULL_VARIANT == frozenset({"sm", "ln", "se"})
