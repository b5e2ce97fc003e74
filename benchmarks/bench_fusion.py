#!/usr/bin/env python
"""Round compression benchmark — eager vs fused flight accounting.

Prices the per-batch proxy op stream on both rings with and without the
flight batcher (mpc/fusion.py) via TraceEngine probes of the one
engine-generic forward, and models the WAN delay of a selection phase
over it (serial and §4.4-scheduled makespan). Emits `BENCH_fusion.json`
— the perf trajectory baseline for the fused MPC path.

`--smoke` additionally EXECUTES a tiny fused phase through the wave
executor and enforces the acceptance gates (CI tier-1 runs this):
  * ExecConfig runs the FUSED stream by default (launch --eager opts out)
  * fused RING32 2PC rounds < eager rounds (>= 40% fewer) at same bytes
  * fused vs eager output shares bitwise identical
  * the fused phase ledger satisfies iosched.ledger_agrees (the
    executed flights against the TraceEngine probe's stream)
  * scale-carrying gate: the RING32 2PC stream's truncation events are
    >= 25% below the frozen per-op-trunc baseline (PER_OP_TRUNC) with
    strictly lower dealer trunc-pair offline bytes —
    `trunc_events` / `offline_nbytes` land in BENCH_fusion.json as the
    regression trajectory

`--protocol 3pc` (the CI 3PC smoke job) runs the 2PC gates above AND
executes both rings under the replicated-3PC backend, additionally
gating:
  * ZERO dealer/offline events in every 3PC ledger (the dealer is dead)
  * fused 3PC rounds strictly below eager at identical bytes

`--protocol aby3trunc` runs the dealer-free gates above under the exact
trunc2 backend; `--protocol spdz2pc` (the CI malicious smoke job) gates
the malicious tier instead: MAC'd offline bytes present, the boundary
mac_key/mac_check records in the eager stream, and fused rounds still
strictly below eager. Every run also emits `malicious_overhead` — the
semi-honest -> malicious cost curve (rounds, online/offline bytes,
truncation events) of each hardened backend against its semi-honest
baseline (spdz2pc vs 2pc, aby3trunc vs 3pc), per ring and fusion mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.configs.base import ArchConfig  # noqa: E402
from repro.core import iosched  # noqa: E402
from repro.core.proxy import ProxySpec  # noqa: E402
from repro.engine import cached_probe, cached_probe_info  # noqa: E402
from repro.mpc import protocols  # noqa: E402
from repro.mpc.comm import PROFILES, WAN, NetProfile  # noqa: E402
from repro.mpc.ring import RING32, RING64  # noqa: E402

RINGS = {"ring64": RING64, "ring32": RING32}

# protocols with no trusted dealer: their ledgers must never carry an
# offline channel or dealer-op records
DEALER_FREE = ("3pc", "aby3trunc")

# each hardened backend and the semi-honest baseline its overhead curve
# is measured against
SEMI_HONEST_OF = {"spdz2pc": "2pc", "aby3trunc": "3pc"}

# (truncation events, dealer trunc-pair bytes) per ring of the per-op
# stream at the smoke geometry, where every mul/matmul/mul_public/mean
# truncated at its own boundary (before scale-carrying shares); never
# update
PER_OP_TRUNC = {"ring64": (23, 688_896), "ring32": (23, 344_448)}


def probe_grid(cfg: ArchConfig, spec: ProxySpec, *, batch: int, seq: int,
               classes: int, n_batches: int,
               protocol: str = "2pc", net: NetProfile = WAN) -> dict:
    """{ring}_{eager|fused} -> per-batch ledger totals + modeled delay.
    The offline (dealer) channel is reported separately — it is the axis
    on which the 3pc backend's zero sits. `net` prices the net_* keys
    (the same profile the socket pacer emulates under --wire); the
    legacy wan_* keys stay pinned to WAN for trajectory comparability."""
    out = {}
    sched = iosched.SchedConfig()
    for rname, ring in RINGS.items():
        for mode, fused in (("eager", False), ("fused", True)):
            t0 = time.time()
            led = cached_probe(cfg, spec, batch=batch, seq=seq,
                               classes=classes, ring=ring,
                               protocol=protocol, fused=fused)
            out[f"{rname}_{mode}"] = {
                "rounds": led.rounds,
                "lat_rounds": led.lat_rounds,
                "bw_rounds": led.bw_rounds,
                "nbytes": led.nbytes,
                "offline_nbytes": led.offline_nbytes,
                "flights": len(led.records),
                "wan_serial_s": led.serial_time(WAN),
                "wan_makespan_s": iosched.makespan(led, n_batches, WAN,
                                                   sched),
                "net": net.name,
                "net_serial_s": led.serial_time(net),
                "net_makespan_s": iosched.makespan(led, n_batches, net,
                                                   sched),
                "probe_ms": (time.time() - t0) * 1e3,
            }
    for rname in RINGS:
        e, f = out[f"{rname}_eager"], out[f"{rname}_fused"]
        out[f"{rname}_round_reduction"] = 1.0 - f["rounds"] / e["rounds"]
    return out


def smoke_execute(protocol: str = "2pc") -> dict:
    """Run a tiny phase for real (eager + fused) and enforce the gates."""
    from benchmarks.common import tiny_exec_setup
    from repro.core.executor import ExecConfig, WaveExecutor

    # the flipped default is itself a gate: deployments run fused unless
    # they explicitly opt out (launch/select.py --eager)
    assert ExecConfig().fuse is True, "ExecConfig.fuse default must be True"

    seq, classes, pool_n, batch, wave = 8, 2, 24, 8, 2
    cfg, spec, pp = tiny_exec_setup(0, seq=seq, n_classes=classes)
    pool = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                             (pool_n, seq))
    key = jax.random.key(7)
    out = {}
    for rname, ring in RINGS.items():
        scores, reports = {}, {}
        for mode, fused in (("eager", False), ("fused", True)):
            ex = WaveExecutor(ExecConfig(wave=wave, batch=batch, ring=ring,
                                         fuse=fused, protocol=protocol))
            ent = ex.score_phase(key, pp, cfg, pool, spec)
            scores[mode], reports[mode] = np.asarray(ent.sh), ex.reports[-1]
        assert np.array_equal(scores["eager"], scores["fused"]), \
            f"{protocol}/{rname}: fusion changed output shares"
        for mode, rep in reports.items():
            assert rep.agrees(), \
                f"{protocol}/{rname}/{mode}: ledger_agrees failed"
        pb = reports["fused"].per_batch
        if protocol in DEALER_FREE:
            # the headline gate: the dealer is DEAD — no offline channel,
            # no dealer ops, anywhere in the executed phase ledger
            for mode, rep in reports.items():
                led = rep.ledger
                assert led.offline_nbytes == 0, \
                    f"{protocol}/{rname}/{mode}: offline bytes in a " \
                    f"dealer-free ledger"
                bad = [r.op for r in led.records
                       if r.tag == "offline" or r.op.startswith("offline")
                       or r.op.startswith("beaver")
                       or r.op.startswith("trunc_open")]
                assert not bad, \
                    f"{protocol}/{rname}/{mode}: dealer events {bad}"
        if protocol == "spdz2pc":
            # the malicious gates: MAC'd dealer randomness present, and
            # the boundary MAC check + key shipment on every ledger (op
            # names survive fusion only on the offline channel and the
            # eager stream — check each where it is visible)
            for mode, rep in reports.items():
                led = rep.ledger
                assert led.offline_nbytes > 0, \
                    f"spdz2pc/{rname}/{mode}: no MAC'd offline bytes"
                assert any(r.op.endswith("mac_key") for r in led.records), \
                    f"spdz2pc/{rname}/{mode}: no MAC-key shipment record"
            eager_ops = [r.op for r in reports["eager"].per_batch.records]
            assert "mac_check" in eager_ops, \
                f"spdz2pc/{rname}: no boundary mac_check in eager stream"
            assert "sacrifice" in eager_ops, \
                f"spdz2pc/{rname}: no triple-sacrifice flight"
        e = reports["eager"].per_batch
        red = 1.0 - pb.rounds / e.rounds
        assert pb.nbytes == e.nbytes, \
            f"{protocol}/{rname}: fusion changed bytes"
        assert pb.rounds < e.rounds, f"{protocol}/{rname}: no round reduction"
        if ring is RING32 and protocol == "2pc":
            assert red >= 0.40, \
                f"ring32 round reduction {red:.2%} below the 40% gate"
        # scale-carrying truncation events: every force is one bw trunc
        # flight in the EAGER stream (trunc_open / trunc_reshare); the
        # dealer pair bytes ride the offline channel in both modes
        trunc_events = sum(1 for r in e.records
                           if r.tag == "bw" and "trunc" in r.op)
        trunc_pair_bytes = sum(r.nbytes for r in pb.records
                               if r.tag == "offline" and "trunc" in r.op)
        base_events, base_bytes = PER_OP_TRUNC[rname]
        trunc_red = 1.0 - trunc_events / base_events
        if ring is RING32 and protocol == "2pc":
            # the scale-carrying gate: cross-op deferred truncation must
            # strip >= 25% of the per-op trunc events AND the dealer's
            # pair bytes versus the frozen per-op stream
            assert trunc_red >= 0.25, \
                f"trunc events {trunc_events} vs PR4 {base_events}: " \
                f"{trunc_red:.2%} below the 25% gate"
            assert trunc_pair_bytes < base_bytes, \
                f"trunc-pair bytes {trunc_pair_bytes} not below PR4 " \
                f"baseline {base_bytes}"
        if (ring is RING64 and trunc_events
                and protocols.get(protocol).exact_trunc):
            # the ring-parameterized headroom cap (scale.cap: 3f fits in
            # 63 bits, so RING64 defers one more truncation than the
            # RING32 2f cap) — the new RING64 floor, for the backends
            # whose truncation is EXACT at any exponent (spdz2pc dealer
            # pairs, aby3trunc trunc2); probabilistic local-trunc
            # backends keep the 2f cap (ops._headroom_bits) and are not
            # gated here. PER_OP_TRUNC stays FROZEN at the per-op
            # stream, so the reduction key tracks the widening
            # gap rather than moving the goalpost
            assert trunc_events <= 16, \
                f"{protocol}/ring64: {trunc_events} trunc events above " \
                f"the 3f-headroom floor of 16"
        if protocol in DEALER_FREE:
            assert pb.offline_nbytes == 0, \
                f"{protocol}/{rname}: folded dealer-free probe carries " \
                f"offline bytes"
        out[rname] = {"eager_rounds": e.rounds, "fused_rounds": pb.rounds,
                      "round_reduction": red, "bitwise_identical": True,
                      "ledger_agrees": True,
                      # host-clock wall time of the fused phase's wave
                      # loop (per-wave stamps, PhaseReport.device)
                      "waves_wall_s": reports["fused"].waves_wall_s,
                      "offline_nbytes": pb.offline_nbytes,
                      "trunc_events": trunc_events,
                      "trunc_events_pr4": base_events,
                      "trunc_event_reduction": trunc_red,
                      "trunc_pair_nbytes": trunc_pair_bytes,
                      "trunc_pair_nbytes_pr4": base_bytes}
    # the ring-cap dividend in one number: how many MORE trunc events
    # the 2f RING32 cap pays than the RING64 cap (1 on the exact-trunc
    # backends spdz2pc/aby3trunc — the only ones allowed the 3f
    # deferral; 0 on 3pc, whose probabilistic local trunc keeps 2f on
    # both rings; 17 on semi-honest 2pc, whose RING64 truncation is
    # recordless-local and never hits the wire at all)
    out["ring64_trunc_event_delta"] = (out["ring32"]["trunc_events"]
                                       - out["ring64"]["trunc_events"])
    return out


def mesh_smoke() -> dict:
    """Execute the RING32 2pc smoke phase on a REAL device mesh
    (forced host devices on CPU CI) and enforce the device-half gates:
      * mesh="host": party axis -> "pod" devices, wave axis -> "data"
        devices via NamedSharding device_put (GSPMD collectives at the
        opens); mesh="shardmap": wave lanes split across the data axis
        under jax.shard_map — BOTH must yield entropy scores bitwise
        identical to the single-device run and ledger_agrees
      * combine="interpret": the fused RING32 Beaver combines must
        demonstrably run through kernels/ops.secure_matmul (kernel-path
        dispatch counter > 0, ref-fallback counter == 0) instead of the
        silent jnp reference
      * waves_wall_s > 0 from the double-buffer loop's per-wave
        host-clock dispatch/ready stamps
    Geometry: 64 candidates / batch 8 / wave 4 -> 2 waves x 4 lanes, so
    the lane count divides the data axis on an 8-device mesh (pod 2 x
    data 4)."""
    from benchmarks.common import tiny_exec_setup
    from repro.core.executor import ExecConfig, WaveExecutor

    seq, classes, pool_n, batch, wave = 8, 2, 64, 8, 4
    cfg, spec, pp = tiny_exec_setup(0, seq=seq, n_classes=classes)
    pool = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                             (pool_n, seq))
    key = jax.random.key(7)
    n_dev = len(jax.devices())
    out = {"n_devices": n_dev}

    ex0 = WaveExecutor(ExecConfig(wave=wave, batch=batch, ring=RING32))
    ref = np.asarray(ex0.score_phase(key, pp, cfg, pool, spec).sh)
    rep0 = ex0.reports[-1]
    assert rep0.agrees(), "mesh: single-device reference ledger diverged"
    out["none"] = {"waves_wall_s": rep0.waves_wall_s,
                   "wall_s": rep0.wall_s}

    for mode in ("host", "shardmap"):
        ex = WaveExecutor(ExecConfig(wave=wave, batch=batch, ring=RING32,
                                     mesh=mode, combine="interpret"))
        ent = ex.score_phase(key, pp, cfg, pool, spec)
        rep = ex.reports[-1]
        dev = rep.device
        assert np.array_equal(ref, np.asarray(ent.sh)), \
            f"mesh={mode}: sharded execution changed entropy scores"
        assert rep.agrees(), f"mesh={mode}: ledger_agrees failed"
        assert dev.waves_wall_s > 0.0, \
            f"mesh={mode}: no wave loop wall time"
        assert dev.combine_kernel > 0, \
            f"mesh={mode}: fused RING32 combines never hit the " \
            f"secure_matmul kernel (interpret mode)"
        assert dev.combine_ref == 0, \
            f"mesh={mode}: {dev.combine_ref} combines silently fell " \
            f"back to the jnp reference"
        if mode == "host" and n_dev >= 2 and n_dev % 2 == 0:
            assert dev.mesh_axes.get("pod") == 2, \
                f"host mesh did not map the 2pc party axis to a pod " \
                f"axis: {dev.mesh_axes}"
        out[mode] = {
            "bitwise_identical": True,
            "ledger_agrees": True,
            "n_devices": dev.n_devices,
            "mesh_axes": dev.mesh_axes,
            "waves_wall_s": dev.waves_wall_s,
            "wall_s": rep.wall_s,
            "combine_kernel": dev.combine_kernel,
            "combine_ref": dev.combine_ref,
            "combine_padded": dev.combine_padded,
            "devices_used": [w.devices_used for w in dev.waves],
        }
    return out


def wire_smoke(wire: str, net: str,
               wire_protocols=("2pc", "3pc")) -> dict:
    """Execute the smoke phase over a REAL transport (repro/net/) and
    enforce the real-wire acceptance gates, per protocol:
      * entropy shares bitwise identical to the ledger-only default path
        (coalesced + fused — the wire run forces the eager schedule, so
        this doubles as a schedule-invariance check)
      * transport-counted bytes == ledger nbytes (record-for-record via
        net.reconcile inside the executor, totals re-asserted here)
      * every party's received-payload digest matches the flight tape
    `wire_makespan_s` is MEASURED wall-clock between the parties' SYNC
    barrier and the last party finishing — under --wire socket the links
    are paced/delayed to emulate `net`, so the number sits next to the
    modeled makespan as an experiment vs its model."""
    from benchmarks.common import tiny_exec_setup
    from repro.core.executor import ExecConfig, WaveExecutor

    seq, classes, pool_n, batch, wave = 8, 2, 24, 8, 2
    cfg, spec, pp = tiny_exec_setup(0, seq=seq, n_classes=classes)
    pool = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                             (pool_n, seq))
    key = jax.random.key(7)
    profile = PROFILES[net]
    out = {"mode": wire, "net": net}
    for proto in wire_protocols:
        ex0 = WaveExecutor(ExecConfig(wave=wave, batch=batch,
                                      protocol=proto))
        ref = np.asarray(ex0.score_phase(key, pp, cfg, pool, spec).sh)
        ex = WaveExecutor(ExecConfig(wave=wave, batch=batch, protocol=proto,
                                     wire=wire, net=net))
        ent = ex.score_phase(key, pp, cfg, pool, spec)
        rep = ex.reports[-1]
        w = rep.wire
        assert w is not None, f"{proto}: wire run produced no WireReport"
        assert np.array_equal(ref, np.asarray(ent.sh)), \
            f"{proto}: wire execution changed entropy scores"
        assert w.bytes_match, \
            f"{proto}: wire bytes {w.wire_nbytes} != tape {w.tape_nbytes}"
        assert w.wire_nbytes == rep.ledger.nbytes, \
            f"{proto}: wire bytes {w.wire_nbytes} != ledger " \
            f"{rep.ledger.nbytes}"
        assert w.digests_ok, f"{proto}: received-payload digests diverged"
        out[proto] = {
            "wire_makespan_s": w.wire_makespan_s,
            "modeled_makespan_s": rep.makespan(profile),
            "nbytes": w.wire_nbytes,
            "flights": w.n_flights,
            "msgs": w.n_msgs,
            "frames": w.n_frames,
            "beats_seen": w.beats_seen,
            "suspects": w.suspects,
            "n_parties": w.n_parties,
            "bitwise_identical": True,
            "bytes_match": True,
            "digests_ok": True,
        }
    return out


def chaos_smoke(wire: str, net: str, seed: int,
                wire_protocols=("2pc", "3pc")) -> dict:
    """Execute the wire smoke under a seeded FaultPlan (net/faults.py)
    and enforce the chaos acceptance gates, per protocol:
      * the replay COMPLETES despite dropped frames, latency spikes, a
        connection reset, and (3pc) a party crash mid-phase
      * entropy scores stay bitwise identical to the fault-free path
      * goodput still reconciles byte-for-byte against the ledger —
        recovery traffic rides the separate RETRANS channel
      * `retries > 0` (losses actually recovered, not dodged) and, when
        the plan crashes a party, `respawns >= 1` / `recovery_time_s > 0`
      * determinism: the same seed over the same tape produces the
        identical fault placement (2pc runs twice and compares plans)
    """
    from benchmarks.common import tiny_exec_setup
    from repro.core.executor import ExecConfig, WaveExecutor

    seq, classes, pool_n, batch, wave = 8, 2, 24, 8, 2
    cfg, spec, pp = tiny_exec_setup(0, seq=seq, n_classes=classes)
    pool = np.random.default_rng(0).integers(0, cfg.vocab_size,
                                             (pool_n, seq))
    key = jax.random.key(7)
    out = {"mode": wire, "net": net, "seed": seed}
    for proto in wire_protocols:
        ex0 = WaveExecutor(ExecConfig(wave=wave, batch=batch,
                                      protocol=proto))
        ref = np.asarray(ex0.score_phase(key, pp, cfg, pool, spec).sh)
        n_runs = 2 if proto == "2pc" else 1    # 2pc doubles as the
        runs = []                              # determinism witness
        for _ in range(n_runs):
            ex = WaveExecutor(ExecConfig(wave=wave, batch=batch,
                                         protocol=proto, wire=wire,
                                         net=net, chaos_seed=seed))
            ent = ex.score_phase(key, pp, cfg, pool, spec)
            runs.append((np.asarray(ent.sh), ex.reports[-1].wire))
        got, w = runs[0]
        assert w is not None and w.faults_injected > 0, \
            f"{proto}: chaos run injected no faults"
        assert np.array_equal(ref, got), \
            f"{proto}: chaos execution changed entropy scores"
        assert w.bytes_match, \
            f"{proto}: goodput {w.wire_nbytes} != tape {w.tape_nbytes} " \
            f"under chaos"
        assert w.digests_ok, f"{proto}: payload digests diverged under chaos"
        assert w.retries > 0, f"{proto}: no retries — faults not exercised"
        plan = json.loads(w.fault_plan)
        if plan.get("crash"):
            assert w.respawns >= 1 or w.degraded, \
                f"{proto}: crashed party neither respawned nor degraded"
            assert w.recovery_time_s > 0, \
                f"{proto}: crash recovered in zero time?"
        for _, w2 in runs[1:]:
            assert w2.fault_plan == w.fault_plan, \
                f"{proto}: same seed produced a different fault placement"
        out[proto] = {
            "faults_injected": w.faults_injected,
            "fault_plan": plan,
            "retries": w.retries,
            "retrans_bytes": w.retrans_bytes,
            "ack_bytes": w.ack_bytes,
            "dup_frames": w.dup_frames,
            "reconnects": w.reconnects,
            "respawns": w.respawns,
            "recovery_time_s": w.recovery_time_s,
            "degraded": w.degraded,
            "dead_parties": w.dead_parties,
            "nbytes": w.wire_nbytes,
            "wire_makespan_s": w.wire_makespan_s,
            "bitwise_identical": True,
            "bytes_match": True,
            "digests_ok": True,
            "deterministic": True,
        }
    return out


def _trunc_events(led) -> int:
    """Protocol-level truncation events in an EAGER stream (trunc_open /
    trunc2 / trunc_reshare); fused streams fold bw op names into their
    flights, so the count is always taken from the eager probe — the
    events themselves are mode-invariant."""
    return sum(1 for r in led.records if r.tag == "bw" and "trunc" in r.op)


def malicious_overhead(cfg: ArchConfig, spec: ProxySpec, *, batch: int,
                       seq: int, classes: int) -> dict:
    """The semi-honest -> hardened cost curve: per-batch TraceEngine
    probes of each hardened backend against its baseline (spdz2pc vs
    2pc, aby3trunc vs 3pc) on both rings and both fusion modes —
    rounds, online bytes, offline (dealer) bytes, truncation events.
    This is what malicious security costs on the wire."""
    out = {}
    for mal, base in SEMI_HONEST_OF.items():
        for rname, ring in RINGS.items():
            leds = {}
            for proto in (mal, base):
                for mode, fused in (("eager", False), ("fused", True)):
                    # memoized: the 2pc/3pc baselines here are the SAME
                    # probes the probe_grid of a matching --protocol run
                    # already paid for (~1 s each)
                    leds[proto, mode] = cached_probe(
                        cfg, spec, batch=batch, seq=seq, classes=classes,
                        ring=ring, protocol=proto, fused=fused)
            te_m = _trunc_events(leds[mal, "eager"])
            te_b = _trunc_events(leds[base, "eager"])
            for mode in ("eager", "fused"):
                lm, lb = leds[mal, mode], leds[base, mode]
                out[f"{mal}_{rname}_{mode}"] = {
                    "baseline": base,
                    "rounds": lm.rounds,
                    "rounds_base": lb.rounds,
                    "rounds_overhead": lm.rounds - lb.rounds,
                    "online_nbytes": lm.nbytes,
                    "online_nbytes_base": lb.nbytes,
                    "online_overhead": lm.nbytes - lb.nbytes,
                    "offline_nbytes": lm.offline_nbytes,
                    "offline_nbytes_base": lb.offline_nbytes,
                    "trunc_events": te_m,
                    "trunc_events_base": te_b,
                }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry + executed acceptance gates (CI)")
    ap.add_argument("--protocol",
                    choices=["2pc", "3pc", "spdz2pc", "aby3trunc"],
                    default="2pc",
                    help="secret-sharing backend to bench; any non-2pc "
                         "choice also re-runs the 2pc gates (the CI 3pc "
                         "and malicious smoke jobs)")
    ap.add_argument("--wire", choices=["none", "local", "socket"],
                    default="none",
                    help="execute the smoke phase over a real transport "
                         "(repro/net/): 'local' = one thread per party "
                         "over in-process queues, 'socket' = one process "
                         "per party over paced localhost TCP emulating "
                         "--net; measures wire_makespan_s and reconciles "
                         "transport bytes against the ledger "
                         "(requires --smoke)")
    ap.add_argument("--mesh", action="store_true",
                    help="execute the smoke phase on a real device mesh "
                         "(party -> pod, wave -> data; forced host "
                         "devices on CPU) in both host-GSPMD and "
                         "shard_map placements, gating bitwise scores, "
                         "ledger agreement, the secure_matmul kernel "
                         "combine path, and the wave loop's waves_wall_s "
                         "(requires --smoke; run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--net", choices=sorted(PROFILES), default="wan",
                    help="NetProfile for BOTH the delay model (net_* "
                         "probe keys) and the socket pacer")
    ap.add_argument("--chaos", action="store_true",
                    help="re-run the wire smoke under a seeded FaultPlan "
                         "(drops, spikes, a connection reset, a party "
                         "crash) and gate recovery: scores bitwise "
                         "identical, goodput reconciled, retries > 0; "
                         "lands in BENCH_fusion.json['chaos'] "
                         "(requires --smoke and --wire)")
    ap.add_argument("--chaos-seed", type=int, default=123,
                    help="FaultPlan seed (same seed + tape = identical "
                         "fault placement)")
    ap.add_argument("--csv", action="store_true",
                    help="emit benchmarks.run CSV rows instead of summary")
    ap.add_argument("--out", default="BENCH_fusion.json")
    args = ap.parse_args(argv)
    if args.wire != "none" and not args.smoke:
        ap.error("--wire requires --smoke (the paper-scale geometry is "
                 "probed analytically, never executed)")
    if args.chaos and args.wire == "none":
        ap.error("--chaos requires --wire local|socket (faults are "
                 "injected into a real transport)")
    if args.mesh:
        if not args.smoke:
            ap.error("--mesh requires --smoke (only the smoke geometry "
                     "is executed on the mesh)")
        # only effective before backend init — the CI job sets XLA_FLAGS
        # in the environment; this covers direct script invocations
        from repro.parallel import sharding as _sharding
        _sharding.force_host_devices(8)

    if args.smoke:
        cfg = ArchConfig(name="fusion-smoke", family="dense", n_layers=1,
                         d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                         d_ff=64, vocab_size=64)
        spec, batch, seq, classes, n_batches = ProxySpec(1, 2, 4), 8, 8, 2, 3
    else:
        # paper scale: BERT-base phase-2 proxy <3, 12, 16> over 42K docs
        cfg = ArchConfig(name="bert-base", family="dense", n_layers=3,
                         d_model=768, n_heads=12, n_kv_heads=12, d_head=64,
                         d_ff=3072, vocab_size=30522)
        spec, batch, seq, classes = ProxySpec(3, 12, 16), 4, 512, 2
        n_batches = -(-42_000 // batch)

    result = {
        "geometry": {"arch": cfg.name, "proxy": dataclasses.asdict(spec),
                     "batch": batch, "seq": seq, "classes": classes,
                     "n_batches": n_batches, "protocol": args.protocol,
                     "net": args.net, "wire": args.wire},
        "probe": probe_grid(cfg, spec, batch=batch, seq=seq,
                            classes=classes, n_batches=n_batches,
                            protocol=args.protocol, net=PROFILES[args.net]),
        # the semi-honest -> malicious overhead curve always ships with
        # the benchmark: it is the trajectory the malicious smoke job
        # gates and the number the threat-model docs quote
        "malicious_overhead": malicious_overhead(cfg, spec, batch=batch,
                                                 seq=seq, classes=classes),
    }
    if args.smoke:
        # the 2pc gates always run (a hardened job must not regress
        # 2pc); any other --protocol adds its own gates on top
        result["smoke"] = smoke_execute("2pc")
        if args.protocol != "2pc":
            result[f"smoke_{args.protocol}"] = smoke_execute(args.protocol)
        if args.mesh:
            # device-mesh gates: wave/party axes physically sharded,
            # kernel-path combines, the wave loop's waves_wall_s
            result["mesh"] = mesh_smoke()
        if args.wire != "none":
            # real-wire gates: both party counts (2pc duplex pair, 3pc
            # ring) cross the transport; wire_makespan_s is measured
            result["wire"] = wire_smoke(args.wire, args.net)
        if args.chaos:
            result["chaos"] = chaos_smoke(args.wire, args.net,
                                          args.chaos_seed)
    ci = cached_probe_info()
    result["probe_cache"] = {"hits": ci.hits, "misses": ci.misses}

    for key, curve in result["malicious_overhead"].items():
        if curve["rounds_overhead"] < 0:
            print(f"FAIL: {key}: hardened backend claims FEWER rounds "
                  f"than its semi-honest baseline", file=sys.stderr)
            return 1
        if key.startswith("aby3trunc_ring64"):
            # exact trunc2 unlocks the 3f headroom deferral the
            # semi-honest 3pc baseline's probabilistic local trunc must
            # forgo (ops._headroom_bits) — hardening strictly REDUCES
            # truncation events here even as rounds stay above baseline
            if curve["trunc_events"] >= curve["trunc_events_base"]:
                print(f"FAIL: {key}: exact-trunc backend did not defer "
                      f"past its semi-honest baseline's 2f cap",
                      file=sys.stderr)
                return 1
    if args.protocol == "spdz2pc":
        off = sum(v["offline_nbytes"] for v in result["probe"].values()
                  if isinstance(v, dict))
        if off == 0:
            print("FAIL: spdz2pc probe carries no MAC'd offline bytes",
                  file=sys.stderr)
            return 1
        for rname in RINGS:
            curve = result["malicious_overhead"][f"spdz2pc_{rname}_eager"]
            if curve["rounds_overhead"] <= 0:
                print(f"FAIL: spdz2pc/{rname}: malicious hardening shows "
                      f"no round overhead (sacrifice/mac_check missing?)",
                      file=sys.stderr)
                return 1
            if curve["offline_nbytes"] <= curve["offline_nbytes_base"]:
                print(f"FAIL: spdz2pc/{rname}: MAC'd offline bytes not "
                      f"above the semi-honest dealer's", file=sys.stderr)
                return 1
        r32 = result["probe"]["ring32_round_reduction"]
        if r32 <= 0.0:
            print("FAIL: fused spdz2pc probe shows no round reduction",
                  file=sys.stderr)
            return 1
    elif args.protocol in DEALER_FREE:
        off = sum(v["offline_nbytes"] for v in result["probe"].values()
                  if isinstance(v, dict))
        if off != 0:
            print(f"FAIL: {args.protocol} probe carries {off} offline "
                  f"dealer bytes", file=sys.stderr)
            return 1
        r32 = result["probe"]["ring32_round_reduction"]
        if r32 <= 0.0:
            print(f"FAIL: fused {args.protocol} probe shows no round "
                  f"reduction", file=sys.stderr)
            return 1
    else:
        r32 = result["probe"]["ring32_round_reduction"]
        if r32 < 0.40:
            print(f"FAIL: fused RING32 probe reduces rounds by only "
                  f"{r32:.2%}", file=sys.stderr)
            return 1
    # merge-update: different CI jobs write different sections (--mesh
    # adds "mesh", --wire adds "wire"/"chaos", --protocol its smoke_*);
    # each run overwrites only the sections it recomputed, so the
    # checked-in artifact accumulates every job's trajectory instead of
    # the last job clobbering the others
    merged = {}
    try:
        with open(args.out) as f:
            merged = json.load(f)
    except (OSError, ValueError):
        pass
    merged.update(result)
    result = merged
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    for k, v in result["probe"].items():
        if args.csv:
            from benchmarks.common import emit
            if isinstance(v, dict):
                emit(f"fusion.{k}", v["probe_ms"] * 1e3,
                     {"rounds": v["rounds"], "nbytes": v["nbytes"],
                      "wan_makespan_s": round(v["wan_makespan_s"], 3)})
            else:
                emit(f"fusion.{k}", 0.0, {"reduction": round(v, 4)})
        elif isinstance(v, dict):
            print(f"{k}: rounds={v['rounds']} bytes={v['nbytes']} "
                  f"wan_makespan={v['wan_makespan_s']:.1f}s")
        else:
            print(f"{k}: {v:.2%}")
    if "mesh" in result and not args.csv:
        mv = result["mesh"]
        for mode in ("host", "shardmap"):
            m = mv[mode]
            print(f"mesh[{mode}] devices={m['n_devices']} "
                  f"axes={m['mesh_axes']} "
                  f"waves_wall={m['waves_wall_s']:.3f}s "
                  f"kernel_combines={m['combine_kernel']} "
                  f"padded={m['combine_padded']}")
    if "wire" in result and not args.csv:
        for proto in ("2pc", "3pc"):
            wv = result["wire"][proto]
            print(f"wire[{result['wire']['mode']}/{result['wire']['net']}] "
                  f"{proto}: measured={wv['wire_makespan_s']:.3f}s "
                  f"modeled={wv['modeled_makespan_s']:.3f}s "
                  f"bytes={wv['nbytes']} flights={wv['flights']}")
    if "chaos" in result and not args.csv:
        for proto in ("2pc", "3pc"):
            cv = result["chaos"][proto]
            print(f"chaos[{result['chaos']['mode']}] {proto}: "
                  f"faults={cv['faults_injected']} retries={cv['retries']} "
                  f"retrans={cv['retrans_bytes']}B "
                  f"respawns={cv['respawns']} "
                  f"recovery={cv['recovery_time_s']:.3f}s "
                  f"degraded={cv['degraded']}")
    if not args.csv:
        print(f"wrote {args.out}")
    return 0


def run() -> None:
    """benchmarks.run harness entry: smoke geometry, CSV rows, and the
    executed acceptance gates (raises on regression)."""
    if main(["--smoke", "--csv"]) != 0:
        raise RuntimeError("fused RING32 round reduction below the gate")


if __name__ == "__main__":
    raise SystemExit(main())
