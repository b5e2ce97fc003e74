"""Wave executor: the §4.4 schedule realized, not just priced.

Three contracts:
  1. LEDGER AGREEMENT — the executor's recorded flights for a phase are
     exactly (integer equality) the inputs iosched.makespan prices, and
     the measured per-batch op stream is the pinned probe stream
     (tests/data/probe_streams.txt) record for record.
  2. SCHEDULE INVARIANCE — the four (coalesce, overlap) variants move
     flights around but never change a single share: scores are bitwise
     identical, so wave execution selects the same survivors as the
     serial path.
  3. PARITY — wave-MPC scores track the clear float path (and selection
     survivors agree between mode="clear" and mode="mpc").
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_targets import TINY_TARGET
from repro.core import iosched
from repro.core import proxy as proxy_mod
from repro.core.executor import ExecConfig, WaveExecutor
from repro.core.proxy import ProxySpec
from repro.mpc import comm, quickselect
from repro.mpc.comm import WAN, Ledger, ledger_scope
from repro.mpc.ring import RING32, x64_scope

from data import probe_streams as streams

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, WAVE, CLASSES = 8, 8, 4, 3
POOL = 48                        # 6 batches -> 2 waves of (4, 2)
K = jax.random.key(0)

VARIANTS = iosched.FIG7_VARIANTS


@pytest.fixture(scope="module")
def pool():
    return np.random.default_rng(0).integers(0, CFG.vocab_size, (POOL, SEQ))


@pytest.fixture(scope="module")
def pp():
    return proxy_mod.random_proxy(K, CFG, SPEC, seq_len=SEQ,
                                  n_classes=CLASSES)


@pytest.fixture(scope="module")
def executed(pp, pool):
    """All four schedule variants run on the same pool with the same
    per-batch keys -> {name: (scores_sh, PhaseReport)}. Pinned to the
    eager (fuse=False) stream: these tests assert the anatomy of the
    uncompressed flight ledger; the fused default is covered by
    tests/test_fusion.py and the bench_fusion smoke gates."""
    out = {}
    for name, (co, ov) in VARIANTS.items():
        ex = WaveExecutor(ExecConfig(wave=WAVE, coalesce=co, overlap=ov,
                                     batch=BATCH, fuse=False))
        ent = ex.score_phase(jax.random.fold_in(K, 1), pp, CFG, pool, SPEC)
        out[name] = (ent, ex.reports[-1])
    return out


# ---------------------------------------------------------------------------
# wave flight accounting primitives
# ---------------------------------------------------------------------------

class TestWaveScope:
    def test_lat_rounds_paid_once_bw_per_batch(self):
        with ledger_scope() as led:
            with comm.wave_scope(4):
                comm.record("cmp", rounds=8, nbytes=432, numel=1, tag="lat")
                comm.record("open", rounds=1, nbytes=100, numel=10,
                            flops=5, tag="bw")
        cmp_rec, open_rec = led.records
        assert (cmp_rec.rounds, cmp_rec.nbytes, cmp_rec.wave) == (8, 4 * 432, 4)
        assert (open_rec.rounds, open_rec.nbytes, open_rec.flops) == \
            (4, 400, 20)

    def test_scope_restores(self):
        with comm.wave_scope(4):
            assert comm.get_wave() == 4
        assert comm.get_wave() == 1


# ---------------------------------------------------------------------------
# 1. ledger agreement
# ---------------------------------------------------------------------------

class TestLedgerAgreement:
    def test_per_batch_probe_matches_analytic_exactly(self, executed):
        streams.assert_matches(
            streams.Case("tiny", "2pc", "ring64", "eager", BATCH),
            executed["ours"][1].per_batch)

    def test_all_variants_agree_with_makespan_inputs(self, executed):
        for name, (_, rep) in executed.items():
            assert rep.agrees(), name

    def test_coalesce_strips_exactly_the_wave_lat_rounds(self, executed):
        pb = executed["ours"][1].per_batch
        n_b = executed["ours"][1].n_batches
        n_w = executed["ours"][1].n_waves
        assert executed["ours"][1].ledger.lat_rounds == n_w * pb.lat_rounds
        assert executed["serial"][1].ledger.lat_rounds == n_b * pb.lat_rounds
        # bytes and bw rounds are schedule-invariant
        for name, (_, rep) in executed.items():
            assert rep.ledger.nbytes == n_b * pb.nbytes, name
            assert rep.ledger.bw_rounds == n_b * pb.bw_rounds, name

    def test_disagreement_detected(self, executed):
        """ledger_agrees is a real check: a dropped flight must fail it."""
        rep = executed["ours"][1]
        broken = Ledger()
        broken.records = rep.ledger.records[:-1]
        assert not iosched.ledger_agrees(broken, rep.per_batch,
                                         rep.n_batches, rep.sched)

    def test_makespan_ordering_realized(self, executed):
        mk = {n: rep.makespan(WAN) for n, (_, rep) in executed.items()}
        assert mk["serial"] >= mk["+coalesce"] >= mk["ours"]
        assert mk["serial"] >= mk["+overlap"] >= mk["ours"]


# ---------------------------------------------------------------------------
# 1b. RING32 through the same engine code path (ROADMAP follow-up)
# ---------------------------------------------------------------------------


class TestRing32:
    @pytest.fixture(scope="class")
    def ring32_report(self, pp, pool):
        ex = WaveExecutor(ExecConfig(wave=WAVE, batch=BATCH, ring=RING32,
                                     fuse=False))
        ent = ex.score_phase(jax.random.fold_in(K, 9), pp, CFG, pool, SPEC)
        return ent, ex.reports[-1]

    def test_ring32_phase_ledger_agrees(self, ring32_report):
        """The dealer-trunc op stream satisfies the same executable
        accounting contract as RING64 — one engine, two rings."""
        ent, rep = ring32_report
        assert rep.agrees()
        assert ent.ring is RING32
        assert np.isfinite(np.asarray(ent.sh)).all()

    def test_ring32_probe_matches_analytic_mirror(self, ring32_report):
        """The executed RING32 per-batch stream is the pinned one,
        dealer trunc_open rounds included."""
        _, rep = ring32_report
        streams.assert_matches(
            streams.Case("tiny", "2pc", "ring32", "eager", BATCH),
            rep.per_batch)

    def test_ring32_pays_trunc_rounds_but_fewer_bytes(self, ring32_report,
                                                      executed):
        """Dealer truncation buys exactness with extra bw rounds; the
        4-byte ring halves every Beaver opening's wire bytes."""
        _, rep32 = ring32_report
        pb64 = executed["ours"][1].per_batch
        pb32 = rep32.per_batch
        assert pb32.bw_rounds > pb64.bw_rounds
        assert pb32.lat_rounds == pb64.lat_rounds
        beaver64 = sum(r.nbytes for r in pb64.records
                       if r.op.startswith("beaver"))
        beaver32 = sum(r.nbytes for r in pb32.records
                       if r.op.startswith("beaver"))
        assert beaver32 * 2 == beaver64

    @pytest.mark.parametrize("ring_name", ["ring64", "ring32"])
    def test_tpu_refuses_ring64_at_construction(self, pp, pool, ring_name,
                                                monkeypatch):
        """XLA has no int64 dot on the TPU: a RING64 phase there fails
        at PhaseRun construction, naming the cause, not deep inside a
        trace; RING32 constructs (the backend is steered in the test)."""
        from repro.core.executor import PhaseRun
        from repro.mpc.ring import RING64
        ring = {"ring64": RING64, "ring32": RING32}[ring_name]
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = ExecConfig(wave=WAVE, batch=BATCH, ring=ring)
        if ring is RING64:
            with pytest.raises(ValueError, match="int64 dot"):
                PhaseRun(cfg, K, pp, CFG, pool, SPEC)
        else:
            assert PhaseRun(cfg, K, pp, CFG, pool, SPEC).ring is RING32


# ---------------------------------------------------------------------------
# 2. schedule invariance / serial equivalence
# ---------------------------------------------------------------------------

class TestEquivalence:
    def test_variants_bitwise_identical_scores(self, executed):
        ref = np.asarray(executed["serial"][0].sh)
        for name, (ent, _) in executed.items():
            assert np.array_equal(ref, np.asarray(ent.sh)), name

    def test_wave_selects_same_survivors_as_serial(self, executed):
        with x64_scope():
            picks = {name: quickselect.top_k_indices(ent, 16, seed=3)
                     for name, (ent, _) in executed.items()}
        for name, idx in picks.items():
            assert np.array_equal(idx, picks["serial"]), name

    def test_wave_matches_clear_proxy(self, executed, pp, pool):
        """Parity of the executed wave path against the float reference."""
        from repro.engine import ClearEngine, proxy_entropy
        from repro.mpc.sharing import reconstruct
        clear = np.asarray(proxy_entropy(ClearEngine(), pp, CFG,
                                         jnp.asarray(pool), SPEC))
        ent, _ = executed["ours"]
        with x64_scope():
            got = np.asarray(reconstruct(ent.sh).astype(jnp.float64)
                             / ent.ring.scale)
        assert np.abs(got - clear).max() < 1e-3
        k = 16
        top_c = set(np.argsort(clear)[-k:].tolist())
        top_m = set(np.argsort(got)[-k:].tolist())
        assert len(top_c & top_m) >= k - 1


# ---------------------------------------------------------------------------
# 3. end-to-end: selection drives the executor
# ---------------------------------------------------------------------------

class TestSelectionIntegration:
    def test_clear_vs_mpc_same_survivors(self):
        from repro.core.selection import SelectionConfig, run_selection
        from repro.core import target as tgt
        from repro.data.tasks import make_classification_task
        task = make_classification_task(5, n_pool=96, n_test=50, seq=8,
                                        vocab=64, n_classes=3)
        cfg = dataclasses.replace(CFG, vocab_size=task.vocab)
        params = tgt.init_classifier(K, cfg, task.n_classes)
        results = {}
        for mode in ("clear", "mpc"):
            sel = SelectionConfig(
                phases=[ProxySpec(1, 2, 2, 1.0)], budget_frac=0.3,
                boot_frac=0.1, mode=mode, score_batch=16,
                exvivo_steps=60, invivo_steps=20, finetune_steps=30,
                executor=ExecConfig(wave=3))
            results[mode] = run_selection(
                K, params, cfg, task.pool_tokens, sel,
                n_classes=task.n_classes,
                boot_labels_fn=lambda i: task.pool_labels[i])
        clear_sel = set(results["clear"].selected.tolist())
        mpc_sel = set(results["mpc"].selected.tolist())
        overlap = len(clear_sel & mpc_sel) / len(clear_sel)
        assert overlap >= 0.9, (overlap, clear_sel ^ mpc_sel)
        # the mpc run must carry executor evidence and it must check out
        reps = results["mpc"].exec_reports
        assert len(reps) == 1
        assert reps[0].agrees()

    def test_exec_config_sched_mirror(self):
        ec = ExecConfig(wave=5, coalesce=False, overlap=True)
        sc = ec.sched()
        assert (sc.wave, sc.coalesce, sc.overlap) == (5, False, True)


# ---------------------------------------------------------------------------
# wave sharding axis
# ---------------------------------------------------------------------------

class TestWaveSharding:
    def test_wave_resolves_to_data_axis(self):
        import jax as _jax
        from jax.sharding import Mesh
        from repro.parallel.sharding import ShardRules, fit_spec
        mesh = Mesh(np.array(_jax.devices()[:1]).reshape(1), ("data",))
        rules = ShardRules(mesh)
        assert rules.resolve("wave") == "data"
        # wave claims the data axis first; batch yields rather than reuse
        spec = fit_spec(rules, (4, 8), ["wave", "batch"])
        assert tuple(spec) == ("data", None)
