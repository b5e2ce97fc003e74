"""SelectFormer workflow driver — the paper's end-to-end pipeline.

Stage 1 bootstrap -> proxy generation -> Stage 2 multi-phase MPC sieve ->
Stage 3 transaction + appraisal -> finetune target on purchased data ->
report test accuracy and the modeled selection delay (WAN profile at
paper scale; pod-DCN profile for the deployment projection).

CPU-scale by default (tiny target + synthetic imbalanced task); the same
driver, pointed at the pod mesh and a real corpus, is the deployment
entry point. Delay numbers come from the calibrated analytic cost model
(mpc/costs.py) scheduled by core/iosched.py — identical formulas to the
executable share-level path, evaluated at the paper's geometry.

--mode mpc runs Stage 2 through the wave executor (core/executor.py)
with an MPCEngine interpreting the unified proxy forward; --ring 32
switches the same code path onto the TPU-native RING32 ring and
--protocol {2pc,3pc,spdz2pc,aby3trunc} picks the secret-sharing backend
(2pc: additive + trusted-dealer Beaver triples, offline bytes reported
separately; 3pc: replicated 2-of-3, dealer-free — zero offline bytes;
spdz2pc: the malicious tier, MAC'd shares that abort on tamper;
aby3trunc: 3pc with ABY3's exact 2-round truncation).
--wave/--no-coalesce/--no-overlap select among Fig 7's four schedule
variants at runtime; openings/reshares are round-compressed into fused
flights by default (mpc/fusion.py) — --eager disables the batcher. The
output includes each phase's realized flight ledger plus its exact
agreement with the makespan model. Re-runs resume from phase
checkpoints (--no-resume disables).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.paper_targets import TINY_TARGET
from repro.core import target as tgt, iosched
from repro.core.executor import ExecConfig
from repro.core.proxy import ProxySpec
from repro.core.selection import SelectionConfig, run_selection
from repro.data.tasks import make_classification_task
from repro.engine import ClearEngine, MPCEngine
from repro.mpc import costs
from repro.mpc.comm import WAN, POD_DCN
from repro.mpc.ring import RING32, RING64


def paper_scale_delay(n_pool: int, budget_frac: float, *, seq: int = 128,
                      layers: int = 12, d_model: int = 768, heads: int = 12,
                      classes: int = 2, batch: int = 8) -> dict:
    """Selection delay at paper geometry (BERT-ish) under both nets."""
    g = costs.BlockGeom(batch=batch, seq=seq, d_model=d_model, heads=heads,
                        d_head=d_model // heads, d_ff=4 * d_model)
    budget = int(budget_frac * n_pool)
    phase1 = costs.selection_phase_cost(
        n_pool, int(0.3 * n_pool),
        costs.BlockGeom(batch, seq, d_model, 1, d_model // heads, 0),
        layers=1, classes=classes, mlp_hidden=2)
    phase2 = costs.selection_phase_cost(
        int(0.3 * n_pool), budget, g, layers=3, classes=classes,
        mlp_hidden=16)
    oracle = costs.oracle_selection_cost(n_pool, budget, g, layers=layers,
                                         classes=classes)
    per_batch1 = costs.selection_phase_cost(
        batch, batch,
        costs.BlockGeom(batch, seq, d_model, 1, d_model // heads, 0),
        1, classes, 2)
    out = {}
    for net_name, net in (("wan", WAN), ("pod_dcn", POD_DCN)):
        sched = iosched.SchedConfig()
        ours = (iosched.makespan(phase1.scaled(batch / n_pool),
                                 -(-n_pool // batch), net, sched)
                + iosched.makespan(phase2.scaled(batch / max(int(0.3 * n_pool), 1)),
                                   -(-int(0.3 * n_pool) // batch), net, sched))
        serial = iosched.SchedConfig(coalesce=False, overlap=False)
        orc = iosched.makespan(oracle.scaled(batch / n_pool),
                               -(-n_pool // batch), net, serial)
        out[net_name] = {"ours_hours": ours / 3600,
                         "oracle_hours": orc / 3600,
                         "speedup": orc / max(ours, 1e-9)}
    return out


def run(seed: int = 0, n_pool: int = 800, budget: float = 0.2,
        mode: str = "clear", finetune_steps: int = 250, *,
        wave: int = 8, coalesce: bool = True, overlap: bool = True,
        fuse: bool = True, score_batch: int = 64, ring_bits: int = 64,
        protocol: str = "2pc", resume: bool = True,
        wire: str = "none", net: str = "wan",
        chaos_seed: int | None = None, degraded: bool = False,
        mesh: str = "none", combine: str = "auto") -> dict:
    task = make_classification_task(seed, n_pool=n_pool, n_test=400,
                                    seq=16, vocab=256, n_classes=4)
    cfg = dataclasses.replace(TINY_TARGET, vocab_size=task.vocab)
    key = jax.random.key(seed)
    params0 = tgt.init_classifier(key, cfg, task.n_classes)

    ring = RING32 if ring_bits == 32 else RING64
    engine = MPCEngine(ring=ring, protocol=protocol) if mode == "mpc" \
        else ClearEngine()
    # phase checkpoints are fingerprinted per run, so one shared dir
    # never resumes a different experiment
    ckpt_dir = os.path.join(tempfile.gettempdir(), "selectformer_phases")
    sel = SelectionConfig(
        phases=[ProxySpec(1, 2, 2, 0.4), ProxySpec(2, 4, 8, 1.0)],
        budget_frac=budget, boot_frac=0.05, engine=engine,
        exvivo_steps=150, invivo_steps=80, finetune_steps=100,
        score_batch=score_batch,
        checkpoint_dir=ckpt_dir, resume=resume,
        executor=ExecConfig(wave=wave, coalesce=coalesce, overlap=overlap,
                            fuse=fuse, protocol=protocol,
                            wire=wire, net=net,
                            chaos_seed=chaos_seed, degraded=degraded,
                            mesh=mesh, combine=combine))
    t0 = time.time()
    res = run_selection(key, params0, cfg, task.pool_tokens, sel,
                        n_classes=task.n_classes,
                        boot_labels_fn=lambda i: task.pool_labels[i])
    sel_time = time.time() - t0

    # realized §4.4 schedule: per-phase flight ledgers, checked against
    # the analytic makespan's inputs (exact integer agreement)
    executed = None
    if mode == "mpc":
        # ledger_agrees: None until at least one phase actually executed
        # this run — a fully-resumed run must not assert a contract it
        # never checked
        executed = {"phases": [],
                    "ledger_agrees": True if res.exec_reports else None,
                    "resumed_phases": res.resumed_phases}
        for rep in res.exec_reports:
            executed["ledger_agrees"] &= rep.agrees()
            # the shared per-phase dict shape (PhaseReport.as_dict) —
            # SERVE_report.json emits the identical keys
            executed["phases"].append(rep.as_dict())

    def finetune_and_eval(idx, tag):
        p, _ = tgt.finetune(jax.random.fold_in(key, 7), params0, cfg,
                            jnp.asarray(task.pool_tokens[idx]),
                            jnp.asarray(task.pool_labels[idx]),
                            steps=finetune_steps)
        return tgt.accuracy(p, cfg, jnp.asarray(task.test_tokens),
                            task.test_labels)

    rng = np.random.default_rng(seed)
    rand_idx = rng.choice(n_pool, size=len(res.selected), replace=False)
    acc_ours = finetune_and_eval(res.selected, "ours")
    acc_rand = finetune_and_eval(rand_idx, "random")

    delays = paper_scale_delay(42_000, budget)
    return {"acc_ours": acc_ours, "acc_random": acc_rand,
            "gain": acc_ours - acc_rand,
            "appraisal_entropy": res.appraisal_entropy,
            "selection_wall_s": sel_time,
            "paper_scale_delay": delays,
            "executed": executed,
            "n_selected": int(len(res.selected))}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pool", type=int, default=800)
    ap.add_argument("--budget", type=float, default=0.2)
    ap.add_argument("--mode", choices=["clear", "mpc"], default="clear")
    ap.add_argument("--wave", type=int, default=8,
                    help="batches coalesced per MPC flight (mode=mpc)")
    ap.add_argument("--score-batch", type=int, default=64)
    ap.add_argument("--no-coalesce", action="store_true",
                    help="disable latency-flight coalescing (fig7 'serial')")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable comm/compute double buffering")
    ap.add_argument("--eager", action="store_true",
                    help="disable the flight batcher (fused round "
                         "compression is the default; mpc/fusion.py)")
    ap.add_argument("--ring", type=int, choices=[64, 32], default=64,
                    help="MPC ring: 64 (CrypTen oracle, CPU only) or 32 "
                         "(the TPU ring)")
    ap.add_argument("--protocol",
                    choices=["2pc", "3pc", "spdz2pc", "aby3trunc"],
                    default="2pc",
                    help="secret-sharing backend: 2pc (additive + "
                         "trusted-dealer Beaver), 3pc (replicated "
                         "2-of-3, dealer-free), spdz2pc (malicious: "
                         "MAC'd shares, aborts on tamper) or aby3trunc "
                         "(3pc with exact ABY3 truncation)")
    ap.add_argument("--no-resume", action="store_true",
                    help="ignore existing phase checkpoints")
    ap.add_argument("--wire", choices=["none", "local", "socket"],
                    default="none",
                    help="execute MPC flights over a real transport "
                         "(repro/net/): 'local' = party threads over "
                         "in-process queues, 'socket' = party processes "
                         "over paced localhost TCP; each phase report "
                         "gains a measured wire_makespan_s (mode=mpc)")
    ap.add_argument("--net", choices=["wan", "pod_dcn", "ici"],
                    default="wan",
                    help="NetProfile the socket transport emulates "
                         "(pacing + injected latency)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a deterministic FaultPlan derived from "
                         "each phase's tape with this seed (drops, "
                         "latency spikes, resets, one crash); requires "
                         "--wire local|socket. Scores stay bitwise "
                         "identical and goodput still reconciles.")
    ap.add_argument("--chaos-plan", type=str, default=None,
                    help="write each wired phase's injected FaultPlan "
                         "as JSON to this path (phase index appended) "
                         "for exact replay")
    ap.add_argument("--degraded", action="store_true",
                    help="with --chaos-seed on an honest-majority "
                         "protocol (3pc/aby3trunc): place the crash at "
                         "a phase boundary and complete 2-of-3 with "
                         "the survivors instead of respawning")
    ap.add_argument("--mesh", choices=["none", "host", "shardmap"],
                    default="none",
                    help="device mesh for the wave executor "
                         "(parallel/sharding.py): 'host' device_puts "
                         "each wave with party -> pod and wave -> data "
                         "over the local devices (set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N on "
                         "CPU); 'shardmap' splits wave lanes across the "
                         "data axis under jax.shard_map. Each phase "
                         "report gains the wave loop's host-clock "
                         "waves_wall_s (mode=mpc)")
    ap.add_argument("--combine", choices=["auto", "pallas", "interpret",
                                          "ref"],
                    default="auto",
                    help="Beaver post-open combine for fused RING32 2pc "
                         "matmuls: the Pallas secure_matmul kernel "
                         "('auto' compiles on TPU, 'interpret' runs the "
                         "kernel body on CPU) or the jnp reference — "
                         "bitwise identical either way")
    args = ap.parse_args()
    out = run(args.seed, args.pool, args.budget, args.mode,
              wave=args.wave, coalesce=not args.no_coalesce,
              overlap=not args.no_overlap, fuse=not args.eager,
              score_batch=args.score_batch,
              ring_bits=args.ring, protocol=args.protocol,
              resume=not args.no_resume, wire=args.wire, net=args.net,
              chaos_seed=args.chaos_seed, degraded=args.degraded,
              mesh=args.mesh, combine=args.combine)
    if out["executed"] is not None:
        ex = out["executed"]
        ph = ex["phases"]
        if ex["resumed_phases"]:
            print(f"[select] resumed {ex['resumed_phases']} phase(s) from "
                  "checkpoints — MPC execution skipped for those "
                  "(re-run with --no-resume to execute everything)")
        if ph:
            print(f"[select] executed {len(ph)} MPC phases, ledger_agrees="
                  f"{ex['ledger_agrees']}; per-phase makespan(WAN) "
                  + ", ".join(f"{p['makespan_wan_s']:.1f}s" for p in ph))
        meshed = [p for p in ph if p.get("device")
                  and p["device"]["placement"] != "none"]
        if meshed:
            d0 = meshed[0]["device"]
            print(f"[select] device mesh ({d0['placement']}): "
                  f"{d0['n_devices']} devices {d0['mesh_axes']}; wave "
                  "loops (host clock) "
                  + ", ".join(f"{p['waves_wall_s']:.3f}s"
                              for p in meshed))
        wired = [p["wire"] for p in ph if p.get("wire")]
        if wired:
            print("[select] real wire (" + wired[0]["mode"] + "): measured "
                  + ", ".join(f"{w['wire_makespan_s']:.3f}s" for w in wired)
                  + f"; bytes reconciled={all(w['bytes_match'] for w in wired)}")
        chaotic = [w for w in wired if w.get("faults_injected")]
        if chaotic:
            print("[select] chaos: "
                  f"{sum(w['faults_injected'] for w in chaotic)} faults, "
                  f"{sum(w['retries'] for w in chaotic)} retries, "
                  f"{sum(w['respawns'] for w in chaotic)} respawns, "
                  f"{sum(w['retrans_bytes'] for w in chaotic)} retrans B, "
                  "recovery "
                  f"{sum(w['recovery_time_s'] for w in chaotic):.3f}s")
            if args.chaos_plan:
                for i, w in enumerate(chaotic):
                    if w.get("fault_plan"):
                        path = f"{args.chaos_plan}.phase{i}.json"
                        with open(path, "w") as f:
                            f.write(w["fault_plan"])
                        print(f"[select] chaos plan -> {path}")
    print(f"[select] ours={out['acc_ours']:.3f} random={out['acc_random']:.3f} "
          f"(+{out['gain']:.3f}); modeled WAN delay "
          f"{out['paper_scale_delay']['wan']['ours_hours']:.1f}h vs oracle "
          f"{out['paper_scale_delay']['wan']['oracle_hours']:.0f}h "
          f"({out['paper_scale_delay']['wan']['speedup']:.0f}x)")


if __name__ == "__main__":
    main()
