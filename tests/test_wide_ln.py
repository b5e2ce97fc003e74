"""LayerNorm statistics at BERT-base width (hidden 768) on RING32.

RING32 holds +-128 at the 24 fractional bits of a product. At hidden
768 the LayerNorm variance sums 768 squares there (~236 at the proxy's
scale) and wraps unless the reduction rule of mpc/scale.py forces the
squares down before the sum; 1/768 encoded whole at 12 bits is 6.25%
low unless the mean splits it into a free 2**-8 fold and an encoded
1/3. Contracts:

  1. PARITY — the RING32 2PC forward agrees with ClearEngine and with
     the benchmark's plain reference (bench/reference.py) at hidden
     768, at the sieve's <1,1,2> and <3,12,16> phases, and so does
     every mean of the forward (each layer's two statistics, the pool).
  2. THE OLD RULE FAILS — with the rule and the split taken out (the
     mean as it was before them), the same comparison fails: the
     variance wraps at every layer, and at <3,12,16> the entropies
     leave the tolerance.
  3. MIRROR — costs.proxy_exec_cost agrees record for record with the
     executed op stream at hidden 768 on every backend, and holds the
     pre-sum truncations the rule fires.

The executor's counters of the rule (`DeviceReport.reduce_*`) and its
`site="presum"` spans are checked in tests/test_spans.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from repro.configs.paper_targets import TINY_TARGET
from repro.core import proxy as proxy_mod
from repro.core.proxy import ProxySpec
from repro.engine import (ClearEngine, MPCEngine, TraceEngine,
                          abstract_shares, proxy_entropy)
from repro.mpc import costs, scale
from repro.mpc.ring import RING32
from repro.mpc.sharing import reveal, share

D = 768
CFG = dataclasses.replace(TINY_TARGET, vocab_size=512, n_layers=12,
                          d_model=D, n_heads=12, n_kv_heads=12, d_head=64,
                          d_ff=3072)
PHASES = {"probe": ProxySpec(1, 1, 2), "score": ProxySpec(3, 12, 16)}
BATCH, SEQ, CLASSES = 1, 16, 2
SEED = 2

# Entropies: RING32 keeps 12 fractional bits, so every forced truncation
# is off by up to 2**-12 = 2.4e-4; three layers of them, compounded
# through the emulators, stay within 20 such steps. The readings at
# this seed are 2e-4 to 9e-4; the wrapped variance reads 0.2 at <3,12,16>.
TOL = 5e-3
# LayerNorm statistics and the pool: a mean is (sum / 2**k at 12 bits)
# times 1/m encoded at 12 bits: 2**-12 of truncation, m * 2**-13 of
# relative encoding error (0.04% for m = 3), plus each summed term's own
# 2**-12; eight LSBs. The wrapped variance is off by ~0.33.
STAT_TOL = 2e-3


class _Means:
    """Keeps every mean the forward takes, in order."""

    def mean(self, x, axis):
        out = super().mean(x, axis)
        self.means.append(out)
        return out


class ClearMeans(_Means, ClearEngine):
    def __init__(self):
        object.__setattr__(self, "means", [])


class MPCMeans(_Means, MPCEngine):
    def __init__(self, key):
        super().__init__(RING32, key=key)
        self.means = []


@pytest.fixture(scope="module")
def data():
    key = jax.random.key(SEED)
    tok = jnp.asarray(np.random.default_rng(SEED).integers(
        0, CFG.vocab_size, (BATCH, SEQ)))
    out = {}
    for name, spec in PHASES.items():
        pp = proxy_mod.random_proxy(key, CFG, spec, seq_len=SEQ,
                                    n_classes=CLASSES)
        pp_sh = proxy_mod.share_proxy(jax.random.fold_in(key, 2), pp,
                                      RING32)
        x = jnp.take(pp["embed"], tok, axis=0) * (CFG.d_model ** 0.5)
        out[name] = (spec, pp, pp_sh,
                     share(jax.random.fold_in(key, 3),
                           x.astype(jnp.float32), RING32))
    return tok, out


def _run(data, phase):
    """(MPC entropies, clear entropies, reference entropies, MPC means,
    clear means) of one batch."""
    tok, by_phase = data
    spec, pp, pp_sh, x_sh = by_phase[phase]
    clear = ClearMeans()
    want = np.asarray(proxy_entropy(clear, pp, CFG, tok, spec))
    ref = reference.score(pp, np.asarray(tok), n_layers=spec.n_layers,
                          n_heads=spec.n_heads, n_kv_heads=CFG.n_kv_heads,
                          d_head=CFG.d_head)
    eng = MPCMeans(jax.random.fold_in(jax.random.key(SEED), 4))
    got = np.asarray(reveal(proxy_entropy(eng, pp_sh, CFG, x_sh, spec)))
    return got, want, ref, eng.means, clear.means


def _stat_errors(mpc_means, clear_means):
    assert len(mpc_means) == len(clear_means)
    return [float(np.abs(np.asarray(reveal(m)) - np.asarray(c)).max())
            for m, c in zip(mpc_means, clear_means)]


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_ring32_forward_matches_clear_and_reference(data, phase):
    got, want, ref, mpc_means, clear_means = _run(data, phase)
    assert np.abs(want - ref).max() < 1e-4
    assert np.abs(got - want).max() < TOL, np.abs(got - want).max()
    assert np.abs(got - ref).max() < TOL, np.abs(got - ref).max()
    errs = _stat_errors(mpc_means, clear_means)
    # mu and var per layer, then the pool
    assert len(errs) == 2 * PHASES[phase].n_layers + 1
    assert max(errs) < STAT_TOL, errs


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_old_rule_fails_the_same_comparison(data, phase, monkeypatch):
    """The mean as it was: no pre-sum force, 1/n encoded whole."""
    monkeypatch.setattr(scale, "sum_plan", lambda s, n, f, bits: 0)
    monkeypatch.setattr(scale, "split_reciprocal", lambda n: (0, n))
    got, want, _, mpc_means, clear_means = _run(data, phase)
    errs = _stat_errors(mpc_means, clear_means)
    var_errs = errs[1:-1:2]
    assert len(var_errs) == PHASES[phase].n_layers
    assert min(var_errs) > 100 * STAT_TOL, errs      # wraps every layer
    if phase == "score":
        assert np.abs(got - want).max() > 10 * TOL


@pytest.mark.parametrize("protocol", ["2pc", "spdz2pc", "3pc", "aby3trunc"])
@pytest.mark.parametrize("fused", [False, True], ids=["eager", "fused"])
def test_mirror_agrees_at_hidden_768(protocol, fused):
    spec = PHASES["score"]
    batch, seq = 2, 128
    pp_sh = abstract_shares(CFG, spec, seq, CLASSES, RING32, protocol)
    led = TraceEngine(RING32, protocol=protocol).probe(
        pp_sh, CFG, spec, (batch, seq, D), fused=fused)
    ana = costs.proxy_exec_cost(batch, seq, D, spec.n_heads,
                                CFG.n_kv_heads, CFG.d_head, spec.mlp_dim,
                                CLASSES, spec.n_layers, ring=RING32,
                                protocol=protocol, fused=fused)
    assert len(led.records) == len(ana.records)
    for got, want in zip(led.records, ana.records):
        assert (got.rounds, got.nbytes, got.numel, got.flops, got.tag) \
            == (want.rounds, want.nbytes, want.numel, want.flops,
                want.tag), (got, want)
    if not fused:
        # var at every layer, mu at layers 2 and 3 (the 2f residual),
        # the pool over 128 positions: six pre-sum truncations, each
        # one flight over all b * s * d elements
        presum = [r for r in ana.records
                  if "presum" in r.op and r.tag == "bw"]
        assert len(presum) == 6
        assert {r.numel for r in presum} == {batch * seq * D}
