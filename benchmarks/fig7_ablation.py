"""Figure 7: delay reduction per technique — P / PM / PMT / Ours.

  P   proxy (3-layer, exact nonlinearities), single phase, serial MPC
  PM  + MLP emulation of nonlinearities
  PMT + multi-phase (cheap phase-1 sieve filters 70%)
  Ours + IO scheduling (coalesce latency-bound ops, overlap comm/compute)

Two sections:
  MODELED   paper geometry (DistilBERT, 42K pool, WAN) via the analytic
            cost model — the headline hours.
  EXECUTED  the four (coalesce, overlap) schedule variants RUN through
            the wave executor (core/executor.py) on a CPU-scale pool.
            Each variant's realized flight ledger must agree with the
            iosched.makespan inputs, built from the TraceEngine probe's
            per-batch stream, to exact integer equality, and all
            variants must produce bitwise-identical scores — that chain
            is what licenses trusting the modeled hours above.

Paper claims IO scheduling buys 1.3-1.4x (PMT -> Ours); MLPs buy orders
of magnitude (P -> PM).
"""
from __future__ import annotations

import numpy as np
import jax

from benchmarks.common import emit, timed, tiny_exec_setup
from repro.core import executor as executor_mod, iosched
from repro.mpc import costs
from repro.mpc.comm import WAN

POOL, SEQ, BATCH, CLASSES = 42_000, 128, 8, 2

# executed section: CPU-scale geometry (the schedule, not the model size,
# is what's under test)
EXEC_POOL, EXEC_SEQ, EXEC_BATCH, EXEC_WAVE = 48, 8, 8, 4


def _modeled(t) -> dict:
    d, h = 768, 12
    dh = d // h
    serial = iosched.SchedConfig(coalesce=False, overlap=False)
    full = iosched.SchedConfig(coalesce=True, overlap=True)
    nb = -(-POOL // BATCH)
    g3 = costs.BlockGeom(BATCH, SEQ, d, h, dh, 0)
    g1 = costs.BlockGeom(BATCH, SEQ, d, 1, dh, 0)

    # P: proxy with exact softmax/LN (no FFN), single phase
    led_p = costs.merge(
        costs.matmul_cost(1, BATCH * SEQ, d, 3 * h * dh, "qkv"),
        costs.matmul_cost(BATCH * h, SEQ, dh, SEQ, "scores"),
        costs.softmax_cost(BATCH * h * SEQ, SEQ),
        costs.matmul_cost(BATCH * h, SEQ, SEQ, dh, "av"),
        costs.matmul_cost(1, BATCH * SEQ, h * dh, d, "out"),
        costs.layernorm_cost(BATCH * SEQ, d),
    )
    led_p = led_p.scaled(3)
    led_p.records.extend(costs.entropy_cost(BATCH, CLASSES).records)
    t_p = iosched.makespan(led_p, nb, WAN, serial)

    # PM: + MLP emulators
    led_pm = costs.proxy_model_cost(g3, 3, CLASSES, 16)
    t_pm = iosched.makespan(led_pm, nb, WAN, serial)

    # PMT: + multiphase (phase1 tiny proxy over full pool, phase2 30%)
    led_ph1 = costs.proxy_model_cost(g1, 1, CLASSES, 2)
    nb1 = nb
    nb2 = -(-int(0.3 * POOL) // BATCH)
    t_pmt = (iosched.makespan(led_ph1, nb1, WAN, serial)
             + iosched.makespan(led_pm, nb2, WAN, serial))

    # Ours: + IO scheduling
    t_ours = (iosched.makespan(led_ph1, nb1, WAN, full)
              + iosched.makespan(led_pm, nb2, WAN, full))

    for name, val in (("P", t_p), ("PM", t_pm), ("PMT", t_pmt),
                      ("ours", t_ours)):
        emit(f"fig7.{name}", t.us, {"hours": round(val / 3600, 1)})
    iosched_gain = t_pmt / t_ours
    emit("fig7.summary", t.us, {
        "mlp_gain": round(t_p / t_pm, 1),
        "multiphase_gain": round(t_pm / t_pmt, 2),
        "iosched_gain": round(iosched_gain, 2),
        "paper_iosched_gain": "1.3-1.4"})
    assert t_p > t_pm > t_pmt > t_ours
    assert 1.15 < iosched_gain < 2.5, iosched_gain
    return {"iosched_gain": iosched_gain, "mlp_gain": t_p / t_pm}


def _executed(t) -> dict:
    cfg, spec, pp = tiny_exec_setup(7, seq=EXEC_SEQ, n_classes=CLASSES)
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size,
                                               (EXEC_POOL, EXEC_SEQ))
    # runs all four variants through the REAL executor; raises if any
    # variant's flight ledger diverges from the makespan inputs or any
    # variant changes the scores
    reports = executor_mod.run_variants(jax.random.key(71), pp, cfg,
                                        tokens, spec, batch=EXEC_BATCH,
                                        wave=EXEC_WAVE)
    mk = {}
    for name, rep in reports.items():
        # exact integer agreement: ledger == makespan inputs == probe
        assert rep.agrees()
        mk[name] = rep.makespan(WAN)
        emit(f"fig7.exec.{name}", t.us, {
            "lat_rounds": rep.ledger.lat_rounds,
            "bw_rounds": rep.ledger.bw_rounds,
            "mbytes": round(rep.ledger.nbytes / 1e6, 2),
            "makespan_wan_s": round(mk[name], 2),
            "wall_s": round(rep.wall_s, 2)})
    # schedule dominance, realized: coalescing strips exactly the
    # latency rounds the model says it strips
    assert mk["serial"] >= mk["+coalesce"] >= mk["ours"]
    assert mk["serial"] >= mk["+overlap"] >= mk["ours"]
    gain = mk["serial"] / mk["ours"]
    emit("fig7.exec.summary", t.us, {
        "exec_iosched_gain": round(gain, 2),
        "ledger_agrees": True})
    return {"exec_iosched_gain": gain}


def run() -> dict:
    with timed() as t:
        out = _modeled(t)
        out.update(_executed(t))
    return out
