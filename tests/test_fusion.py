"""Flight fusion: round-compressed MPC streams (mpc/fusion.py).

Contracts:
  1. ACCOUNTING ONLY — fusion moves records, never values: fused vs
     eager output shares are bitwise identical across every Table-2/3
     variant on both rings, at identical bytes-on-wire.
  2. COMPRESSION — the RING32 proxy forward under flight_scope records
     >= 40% fewer ledger rounds than the eager path (the dealer-trunc
     and Beaver openings fold into per-group flights).
  3. PROBE — the fused probe pays fewer rounds at the same bytes, is
     hermetic against ambient wave scopes and wire tapes, and an
     executed fused phase reproduces its pinned stream
     (tests/data/probe_streams.txt) and satisfies iosched.ledger_agrees.
  4. HOT PATH — MPCEngine.matmul's RING32 combine routes through the
     Pallas secure_matmul kernel bitwise-identically (ref + interpret).
"""
import contextlib
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_targets import TINY_TARGET
from repro.core import proxy as proxy_mod
from repro.core.executor import ExecConfig, WaveExecutor
from repro.core.proxy import ProxySpec
from repro.engine import MPCEngine, TraceEngine, VARIANTS, abstract_shares, \
    cached_probe, proxy_entropy
from repro.mpc import comm, fusion, ops as mops, quickselect
from repro.mpc.comm import ledger_scope
from repro.mpc.ring import RING32, RING64, x64_scope
from repro.mpc.sharing import share

from data import probe_streams as streams

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, CLASSES = 8, 6, 3
K = jax.random.key(0)

RINGS = {"ring64": RING64, "ring32": RING32}


def _ring_ctx(ring):
    return x64_scope() if ring.bits >= 64 else contextlib.nullcontext()


@pytest.fixture(scope="module")
def pp():
    return proxy_mod.random_proxy(K, CFG, SPEC, seq_len=SEQ,
                                  n_classes=CLASSES)


@pytest.fixture(scope="module")
def tok():
    return jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (BATCH, SEQ)))


def _run_forward(pp, tok, ring, variant, fused):
    """One MPC forward; returns (shares ndarray, Ledger)."""
    with _ring_ctx(ring):
        pp_sh = proxy_mod.share_proxy(jax.random.fold_in(K, 2), pp, ring)
        x = jnp.take(pp["embed"], tok, axis=0) * (CFG.d_model ** 0.5)
        x_sh = share(jax.random.fold_in(K, 3), x.astype(jnp.float32), ring)
        eng = MPCEngine(ring).with_key(jax.random.fold_in(K, 4))
        with ledger_scope() as led, fusion.flight_scope(enabled=fused):
            out = proxy_entropy(eng, pp_sh, CFG, x_sh, SPEC, variant)
        return np.asarray(out.sh), led


# ---------------------------------------------------------------------------
# batcher unit semantics
# ---------------------------------------------------------------------------

class TestBatcher:
    def test_bw_openings_fuse_to_one_flight(self):
        with ledger_scope() as led, fusion.flight_scope():
            comm.record("a", rounds=1, nbytes=100, numel=10, flops=1)
            comm.record("b", rounds=1, nbytes=200, numel=20, flops=2)
        (rec,) = led.records
        assert (rec.op, rec.rounds, rec.nbytes, rec.numel, rec.flops,
                rec.tag) == ("fused.flight", 1, 300, 30, 3, "bw")

    def test_lat_record_is_a_barrier(self):
        with ledger_scope() as led, fusion.flight_scope():
            comm.record("open1", rounds=1, nbytes=100, numel=1)
            comm.record("cmp", rounds=8, nbytes=432, numel=1, tag="lat")
            comm.record("open2", rounds=1, nbytes=50, numel=1)
        ops_seen = [(r.op, r.rounds, r.nbytes) for r in led.records]
        assert ops_seen == [("fused.flight", 1, 100), ("cmp", 8, 432),
                            ("fused.flight", 1, 50)]

    def test_fused_group_bounds_and_labels(self):
        with ledger_scope() as led, fusion.flight_scope():
            comm.record("ambient", rounds=1, nbytes=10, numel=1)
            with fusion.fused_group("qkv"):
                comm.record("q", rounds=1, nbytes=1, numel=1)
                comm.record("k", rounds=1, nbytes=2, numel=1)
            comm.record("tail", rounds=1, nbytes=5, numel=1)
        assert [(r.op, r.nbytes) for r in led.records] == \
            [("fused.flight", 10), ("fused.qkv", 3), ("fused.flight", 5)]

    def test_fused_group_without_scope_is_noop(self):
        with ledger_scope() as led:
            with fusion.fused_group("qkv"):
                comm.record("q", rounds=1, nbytes=1, numel=1)
        assert [(r.op, r.rounds) for r in led.records] == [("q", 1)]

    def test_lat_scope_coalesces_comparison_batches(self):
        with ledger_scope() as led, fusion.lat_scope("qs"):
            comm.record("cmp", rounds=8, nbytes=432, numel=1, tag="lat")
            comm.record("cmp", rounds=8, nbytes=864, numel=2, tag="lat")
        (rec,) = led.records
        assert (rec.op, rec.rounds, rec.nbytes, rec.numel, rec.tag) == \
            ("fused.qs", 8, 1296, 3, "lat")

    def test_wave_scaling_applies_at_flush(self):
        """Fused flights are per-batch flights: under wave_scope(W) the
        flush scales exactly like the eager bw records it replaces."""
        with ledger_scope() as led, comm.wave_scope(4):
            with fusion.flight_scope():
                comm.record("a", rounds=1, nbytes=100, numel=10)
                comm.record("b", rounds=1, nbytes=100, numel=10)
        (rec,) = led.records
        assert (rec.rounds, rec.nbytes, rec.numel) == (4, 800, 80)

    def test_scope_exit_restores_eager(self):
        with ledger_scope() as led:
            with fusion.flight_scope():
                comm.record("in", rounds=1, nbytes=1, numel=1)
            comm.record("out", rounds=1, nbytes=1, numel=1)
        assert [r.op for r in led.records] == ["fused.flight", "out"]


# ---------------------------------------------------------------------------
# cross-op deferred truncation: scale-carrying shares retired PendingShare
# ---------------------------------------------------------------------------

class TestScaleCarriedTrunc:
    def test_pending_share_is_retired(self):
        """`lazy=True`/PendingShare is gone: the carried exponent on
        Share itself (mpc/scale.py) is the pending-trunc state now."""
        assert not hasattr(fusion, "PendingShare")
        assert not hasattr(fusion, "force")

    @pytest.mark.parametrize("ring", list(RINGS.values()),
                             ids=list(RINGS))
    def test_mul_emits_summed_scale_force_resolves(self, ring):
        with _ring_ctx(ring):
            k = jax.random.fold_in(K, 11)
            x = share(jax.random.fold_in(K, 12),
                      jnp.linspace(-2.0, 2.0, 12).reshape(3, 4), ring)
            y = share(jax.random.fold_in(K, 13),
                      jnp.linspace(0.5, 1.5, 12).reshape(3, 4), ring)
            z = mops.mul(x, y, k)
            assert z.fb == 2 * ring.frac_bits      # raw product scale
            forced = mops.force(z, jax.random.fold_in(K, 14))
            assert forced.fb == ring.frac_bits
            # decode-at-scale: both views reveal the same product
            from repro.mpc.sharing import reveal
            assert np.allclose(np.asarray(reveal(z)),
                               np.asarray(reveal(forced)),
                               atol=4.0 / ring.scale)
            # the memo: forcing twice truncates once
            assert mops.force(z, jax.random.fold_in(K, 15)) is forced


# ---------------------------------------------------------------------------
# 1+2: bitwise parity and >=40% RING32 compression, all variants
# ---------------------------------------------------------------------------

class TestFusedParity:
    @pytest.mark.parametrize("ring", list(RINGS.values()), ids=list(RINGS))
    @pytest.mark.parametrize("vname", sorted(VARIANTS))
    def test_fused_matches_eager_bitwise(self, vname, ring, pp, tok):
        variant = VARIANTS[vname]
        sh_e, led_e = _run_forward(pp, tok, ring, variant, fused=False)
        sh_f, led_f = _run_forward(pp, tok, ring, variant, fused=True)
        assert np.array_equal(sh_e, sh_f), vname
        assert led_f.nbytes == led_e.nbytes, vname
        assert led_f.flops == led_e.flops, vname
        assert led_f.lat_rounds == led_e.lat_rounds, vname
        assert led_f.rounds < led_e.rounds, vname

    def test_ring32_forward_cuts_rounds_40pct(self, pp, tok):
        """The acceptance gate: dealer-trunc + Beaver openings fold into
        per-group flights — >= 40% fewer ledger rounds, bytes unchanged."""
        _, led_e = _run_forward(pp, tok, RING32, VARIANTS["full"], False)
        _, led_f = _run_forward(pp, tok, RING32, VARIANTS["full"], True)
        assert led_f.nbytes == led_e.nbytes
        assert 1 - led_f.rounds / led_e.rounds >= 0.40


# ---------------------------------------------------------------------------
# 3: the fused probe + executed fused phase
# ---------------------------------------------------------------------------

class TestFusedMirror:
    def test_mirror_is_hermetic_under_wave_scope(self):
        """The probe prices ONE batch whatever scope it is called in:
        inside a wave_scope and under a live WireTape it returns the
        same stream and appends nothing to the tape."""
        pp_sh = abstract_shares(CFG, SPEC, SEQ, CLASSES, RING32)

        def probe():
            return TraceEngine(RING32).probe(pp_sh, CFG, SPEC,
                                             (BATCH, SEQ, CFG.d_model),
                                             fused=True)

        outside = probe()
        tape = comm.WireTape(2)
        with comm.wave_scope(4), comm.wire_tape_scope(tape):
            inside = probe()
        assert inside.records == outside.records
        assert tape.flights == []
        streams.assert_matches(
            streams.Case("tiny", "2pc", "ring32", "fused", BATCH), inside)

    def test_fused_mirror_strictly_fewer_rounds_same_bytes(self):
        kw = dict(batch=BATCH, seq=SEQ, classes=CLASSES)
        for ring in RINGS.values():
            eager = cached_probe(CFG, SPEC, **kw, ring=ring)
            fused = cached_probe(CFG, SPEC, **kw, ring=ring, fused=True)
            assert fused.rounds < eager.rounds
            assert fused.nbytes == eager.nbytes
            assert fused.lat_rounds == eager.lat_rounds


class TestExecutedFusedPhase:
    POOL = 24

    @pytest.fixture(scope="class")
    def pool(self):
        return np.random.default_rng(0).integers(0, CFG.vocab_size,
                                                 (self.POOL, SEQ))

    @pytest.fixture(scope="class")
    def executed(self, pp, pool):
        out = {}
        for name, fuse in (("eager", False), ("fused", True)):
            ex = WaveExecutor(ExecConfig(wave=2, batch=8, ring=RING32,
                                         fuse=fuse))
            ent = ex.score_phase(jax.random.fold_in(K, 9), pp, CFG, pool,
                                 SPEC)
            out[name] = (np.asarray(ent.sh), ex.reports[-1])
        return out

    def test_fused_phase_ledger_agrees(self, executed):
        """iosched.ledger_agrees holds for the round-compressed phase:
        the fused per-batch probe is exactly what the schedule prices."""
        assert executed["fused"][1].agrees()

    def test_fusion_does_not_change_scores(self, executed):
        assert np.array_equal(executed["eager"][0], executed["fused"][0])

    def test_fused_per_batch_matches_mirror(self, executed):
        """The executed phase's per-batch stream is the pinned one."""
        streams.assert_matches(
            streams.Case("tiny", "2pc", "ring32", "fused", 8),
            executed["fused"][1].per_batch)

    def test_fused_phase_pays_fewer_rounds(self, executed):
        led_e = executed["eager"][1].ledger
        led_f = executed["fused"][1].ledger
        assert led_f.rounds < led_e.rounds
        assert led_f.nbytes == led_e.nbytes


# ---------------------------------------------------------------------------
# 4: the Pallas combine kernel on the RING32 matmul hot path
# ---------------------------------------------------------------------------

class TestKernelCombine:
    def _operands(self):
        x = share(jax.random.fold_in(K, 21),
                  jnp.asarray(np.random.default_rng(2).normal(
                      size=(16, 8)) * 0.3, jnp.float32), RING32)
        y = share(jax.random.fold_in(K, 22),
                  jnp.asarray(np.random.default_rng(3).normal(
                      size=(8, 8)) * 0.3, jnp.float32), RING32)
        return x, y

    @pytest.mark.parametrize("impl", ["ref", "interpret"])
    def test_kernel_combine_bitwise_equals_inline(self, impl):
        x, y = self._operands()
        k = jax.random.fold_in(K, 23)
        inline = mops.matmul(x, y, k)
        kern = mops.matmul(x, y, k, combine_impl=impl)
        assert np.array_equal(np.asarray(inline.sh), np.asarray(kern.sh))

    def test_engine_routes_ring32_matmul_through_kernel(self):
        x, y = self._operands()
        eng = MPCEngine(RING32, combine_impl="interpret").with_key(
            jax.random.fold_in(K, 24))
        ref = MPCEngine(RING32).with_key(jax.random.fold_in(K, 24))
        got = eng.matmul(x, y)
        want = ref.matmul(x, y)
        assert np.array_equal(np.asarray(got.sh), np.asarray(want.sh))

    def test_ring64_keeps_inline_combine(self):
        """The kernel is int32: a RING64 engine must not route to it."""
        with x64_scope():
            x = share(jax.random.fold_in(K, 25),
                      jnp.ones((4, 4), jnp.float32), RING64)
            eng = MPCEngine(RING64).with_key(jax.random.fold_in(K, 26))
            out = eng.matmul(x, x)
            assert out.sh.dtype == RING64.dtype


# ---------------------------------------------------------------------------
# QuickSelect per-wave comparison coalescing
# ---------------------------------------------------------------------------

class TestQuickselectWaves:
    @pytest.fixture(scope="class")
    def scores(self):
        with x64_scope():
            vals = jnp.asarray(np.random.default_rng(5).normal(size=48),
                               jnp.float32)
            return share(jax.random.fold_in(K, 31), vals)

    def test_wave_chunking_preserves_selection(self, scores, x64):
        base = quickselect.top_k_indices(scores, 16, seed=3)
        for wave in (2, 4, 7):
            got = quickselect.top_k_indices(scores, 16, seed=3, wave=wave)
            assert np.array_equal(base, got), wave

    def test_wave_batches_ride_one_flight(self, scores, x64):
        """Per-wave reveal_lt batches coalesce: a wave-chunked partition
        pays the same rounds as the unchunked one, bytes unchanged."""
        with ledger_scope() as led1:
            quickselect.top_k_indices(scores, 16, seed=3)
        with ledger_scope() as led4:
            quickselect.top_k_indices(scores, 16, seed=3, wave=4)
        assert led4.lat_rounds == led1.lat_rounds
        assert led4.nbytes == led1.nbytes
        assert all(r.tag == "lat" for r in led4.records)

    def test_quickselect_cost_prices_coalescing(self):
        r1, b1 = quickselect.quickselect_cost(1000)
        rc, bc = quickselect.quickselect_cost(1000, wave=8)
        re, be = quickselect.quickselect_cost(1000, wave=8, coalesce=False)
        assert rc == r1 and bc == b1        # coalesced: wave-invariant
        assert re == 8 * r1 and be == b1    # eager: a flight per chunk


# ---------------------------------------------------------------------------
# schedule search prices the executed (fused) stream
# ---------------------------------------------------------------------------

class TestScheduleSearchProbes:
    def test_fused_pricing_is_cheaper_on_ring32(self):
        from repro.core.schedule_search import schedule_delay
        ph = (ProxySpec(1, 1, 2, 1.0),)
        fused = schedule_delay(ph, 4_000, 800, ring=RING32, fused=True)
        eager = schedule_delay(ph, 4_000, 800, ring=RING32, fused=False)
        assert fused < eager

    def test_default_pricing_tracks_executor_default(self):
        """fused=None must price the stream ExecConfig actually runs."""
        from repro.core.schedule_search import schedule_delay
        ph = (ProxySpec(1, 1, 2, 1.0),)
        default = schedule_delay(ph, 4_000, 800, ring=RING32)
        explicit = schedule_delay(ph, 4_000, 800, ring=RING32,
                                  fused=ExecConfig().fuse)
        assert default == explicit

    def test_probe_pricing_matches_trace_engine(self):
        """schedule_delay's per-phase ledger IS a TraceEngine probe of
        the executed stream (not proxy_model_cost's paper geometry)."""
        from repro.core.schedule_search import _phase_probe
        led = _phase_probe(1, 2, 4, d_model=32, heads=2, classes=2,
                           seq=8, batch=4, ring=RING64, fused=True)
        cfg = dataclasses.replace(CFG, n_heads=2, n_kv_heads=2, d_head=16)
        pp_sh = abstract_shares(cfg, ProxySpec(1, 2, 4), 8, 2, RING64)
        want = TraceEngine(RING64).probe(pp_sh, cfg, ProxySpec(1, 2, 4),
                                         (4, 8, cfg.d_model), fused=True)
        assert (led.rounds, led.nbytes, led.flops) == \
            (want.rounds, want.nbytes, want.flops)
