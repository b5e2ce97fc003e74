"""Wave-pipelined MPC executor — the §4.4 schedule, executable.

`core/iosched.py` prices the paper's parallel multiphase schedule; this
module RUNS it. The Stage-2 sieve's candidate batches are grouped into
waves of W:

  COALESCE   the share-level proxy forward is `vmap`ped across the wave,
             so every latency-bound flight (comparisons inside the
             low-dim MLP ReLUs) is ONE stacked message for W batches —
             rounds are paid per wave, bytes per batch. Bandwidth-bound
             Beaver openings remain one flight per batch (their wire
             time, not their RTTs, is the cost; see comm.record).
  OVERLAP    waves are double-buffered: wave i+1 is dispatched before
             blocking on wave i, so batch i's wire/collective time hides
             behind batch i+1's local compute (JAX async dispatch on one
             host; async inter-pod collectives on the TPU mesh).

Accounting is part of the execution contract: every flight lands in the
ambient Ledger through comm.wave_scope, and the phase ledger must satisfy
`iosched.ledger_agrees` — the same integers the analytic makespan prices.
The per-batch reference ledger comes from `engine.TraceEngine` — the
abstract `jax.eval_shape` probe of the identical op stream (zero FLOPs
spent) — which in turn is pinned record-for-record to
`mpc/costs.proxy_exec_cost`.  The forward itself is the unified
engine-generic one (`engine/forward.py`) interpreted by an `MPCEngine`
over this executor's ring and protocol backend; RING64 and RING32 run
the same code path, and so do the additive-2PC (dealer Beaver) and
replicated-3PC (dealer-free) sharing schemes — `ExecConfig.protocol`
picks the backend, the party axis sizes itself accordingly.

On a pod mesh the wave dimension is a logical sharding axis ("wave" ->
the data axis; parallel/sharding.py), so W concurrent batches land on
separate devices and the stacked flights become per-device collectives.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time

import numpy as np
import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import iosched
from repro.core import proxy as proxy_mod
from repro.core.proxy import ProxySpec
from repro.engine import MPCEngine, cached_probe, proxy_entropy
from repro.engine.base import FULL_VARIANT
from repro.spans import span
from repro.mpc import comm, fusion, protocols, ops as mops
from repro.mpc.comm import DeviceReport, Ledger, NetProfile, WaveTiming
from repro.mpc.protocols import additive2pc
from repro.mpc.ring import RING64, RingSpec, x64_scope
from repro.mpc.sharing import AShare, share
from repro.parallel import sharding

# serial number of each PhaseRun: the `run` of its spans
_RUN_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Runtime knobs for one executor — mirrors iosched.SchedConfig so a
    measured phase can be priced by the identical schedule."""
    wave: int = 8                 # batches coalesced per flight
    coalesce: bool = True
    overlap: bool = True
    batch: int = 64               # candidates per batch
    flops_per_s: float = 10e12
    ring: RingSpec = RING64
    # secret-sharing protocol backend (mpc/protocols/): "2pc" additive
    # with trusted-dealer triples, "3pc" replicated 2-of-3, dealer-free
    protocol: str = "2pc"
    # round compression (mpc/fusion.py): run each batch's forward under
    # a flight_scope so independent openings share flights. The
    # per-batch probe is fused identically, so ledger_agrees still holds
    # and the schedule prices the compressed stream. Default ON now that
    # fig7/table4 report both modes; pass fuse=False (launch --eager)
    # for the uncompressed stream.
    fuse: bool = True
    # real-wire execution (repro/net/): "none" keeps flights modeled;
    # "local" replays the captured flight tape as one thread per party
    # over in-process queues; "socket" spawns one PROCESS per party over
    # paced localhost TCP emulating `net`'s profile and measures
    # wall-clock (PhaseReport.wire). Wire capture needs concrete message
    # tensors, so the executor forces coalesce=False under wire modes —
    # scores are schedule-invariant (run_variants proves it bitwise).
    wire: str = "none"
    # which comm.PROFILES entry prices the model AND paces the socket
    net: str = "wan"
    # chaos (net/faults.py): with a seed, derive a deterministic
    # FaultPlan from the captured tape and replay under injected faults
    # (reliable delivery + crash recovery engage automatically); scores
    # must stay bitwise identical and goodput must still reconcile.
    chaos_seed: int | None = None
    # degraded 2-of-3: a 3PC party that dies at a phase boundary is
    # dropped and the survivors finish the opens (replicated sharing)
    degraded: bool = False
    # device mesh (parallel/sharding.py): "none" runs single-device;
    # "host" builds a party x wave mesh over the local devices (forced
    # host devices on CPU CI) and device_puts each wave's shares with
    # party -> "pod", wave -> "data" — eager ops then run under GSPMD
    # with cross-party collectives inserted at the opens; "shardmap"
    # splits the wave lanes across the data axis under jax.shard_map
    # (party replicated per device, one jit per wave so ledger records
    # still fire every wave). Scores are bitwise identical in all three.
    mesh: str = "none"
    # Beaver post-open combine implementation for fused RING32 2PC
    # matmuls (kernels/ops.secure_matmul): "auto" compiles the Pallas
    # kernel on TPU and uses the jnp ref elsewhere; "interpret" runs the
    # kernel body on CPU (CI's witness that the kernel path is live);
    # "ref" forces the reference. Bitwise-identical int32 ring
    # arithmetic in every mode.
    combine: str = "auto"

    def sched(self) -> iosched.SchedConfig:
        return iosched.SchedConfig(coalesce=self.coalesce,
                                   overlap=self.overlap,
                                   wave=max(1, self.wave),
                                   flops_per_s=self.flops_per_s)


@dataclasses.dataclass
class PhaseReport:
    """What one executed sieve phase put on the wire."""
    ledger: Ledger                # realized flights, whole phase
    per_batch: Ledger             # one batch's op stream (probe)
    n_batches: int
    n_waves: int
    wall_s: float
    sched: iosched.SchedConfig
    # how the stream was produced — what the analytic mirror must be
    # parameterized with to reproduce it (benchmarks/common.assert_mirror)
    ring: RingSpec = RING64
    protocol: str = "2pc"
    fused: bool = True
    # real-wire outcome (net.WireReport) when the phase ran with
    # ExecConfig.wire != "none": measured wire_makespan_s, reconciled
    # byte counts, payload digests
    wire: object | None = None
    # device-side outcome (comm.DeviceReport): per-wave dispatch/ready
    # host-clock stamps from the double-buffer loop, mesh placement, and
    # the secure_matmul kernel-vs-ref dispatch counters for the phase
    device: DeviceReport | None = None

    @property
    def waves_wall_s(self) -> float:
        """Host-clock wall time of the wave loop (first wave's forward
        start -> last wave ready)."""
        return self.device.waves_wall_s if self.device else 0.0

    def agrees(self) -> bool:
        """Realized flights == the makespan model's inputs, exactly."""
        return iosched.ledger_agrees(self.ledger, self.per_batch,
                                     self.n_batches, self.sched)

    def makespan(self, net: NetProfile) -> float:
        """Modeled end-to-end delay of this phase's measured op stream."""
        return iosched.makespan(self.per_batch, self.n_batches, net,
                                self.sched)

    def as_dict(self, net: NetProfile | None = None) -> dict:
        """The per-phase report dict every driver emits — launch/select's
        SELECT_report and serve's SERVE_report share this one shape so
        downstream tooling reads both. `makespan_wan_s` stays pinned to
        the WAN profile as the trajectory key; pass `net` to price the
        same stream under another comm.PROFILES entry (adds net_*)."""
        d = {
            "n_batches": self.n_batches, "n_waves": self.n_waves,
            "protocol": self.protocol,
            "lat_rounds": self.ledger.lat_rounds,
            "bw_rounds": self.ledger.bw_rounds,
            "nbytes": self.ledger.nbytes,
            "offline_nbytes": self.ledger.offline_nbytes,
            "makespan_wan_s": self.makespan(comm.PROFILES["wan"]),
            "wall_s": self.wall_s,
            # wave loop wall time + mesh placement
            # (comm.DeviceReport; per-wave stamps in "device")
            "waves_wall_s": self.waves_wall_s,
            "device": self.device.as_dict() if self.device is not None
                      else None,
            # real-wire measurement when ExecConfig.wire != "none"
            "wire": self.wire.as_dict() if self.wire is not None
                    else None,
        }
        if net is not None and net.name != "wan":
            d["net"] = net.name
            d["net_makespan_s"] = self.makespan(net)
        return d


class WaveExecutor:
    """Runs the Stage-2 multiphase sieve through the §4.4 schedule."""

    def __init__(self, cfg: ExecConfig):
        if cfg.wire not in ("none", "local", "socket"):
            raise ValueError(f"unknown wire mode {cfg.wire!r}")
        if cfg.mesh not in ("none", "host", "shardmap"):
            raise ValueError(f"unknown mesh mode {cfg.mesh!r}")
        if cfg.mesh == "shardmap" and cfg.wire != "none":
            # wire capture forces the eager per-lane schedule; shard_map
            # needs the coalesced wave — the host (GSPMD) mesh composes
            # with wire capture, the shard_map one cannot
            raise ValueError("mesh='shardmap' needs the coalesced "
                             "schedule; use mesh='host' with --wire")
        if cfg.chaos_seed is not None and cfg.wire == "none":
            raise ValueError("chaos_seed needs a real wire "
                             "(wire='local' or 'socket')")
        if cfg.wire != "none" and cfg.coalesce:
            # capturing real message tensors requires the eager per-lane
            # path (vmap abstracts the payloads away); the schedule is
            # score-invariant, so this changes WHEN flights happen, not
            # what they carry
            cfg = dataclasses.replace(cfg, coalesce=False)
        self.cfg = cfg
        self.reports: list[PhaseReport] = []

    # -- the schedule ----------------------------------------------------
    def score_phase(self, key, pp, arch_cfg: ArchConfig, tokens,
                    spec: ProxySpec, variant=FULL_VARIANT) -> AShare:
        """Encrypted entropy for every candidate, executed wave-by-wave.

        Identical numerics across all four (coalesce, overlap) variants:
        per-batch PRNG keys and share masks are assigned once, so the
        schedule changes only WHEN flights happen, never their contents.
        """
        run = PhaseRun(self.cfg, key, pp, arch_cfg, tokens, spec, variant)
        for wi in range(run.n_waves):
            run.dispatch(wi)
        run.drain()
        ent, rep = run.finish()
        self.reports.append(rep)
        return ent


class PhaseRun:
    """One sieve phase as a STEPWISE schedule — the §4.4 wave loop with
    the loop inverted out.

    `WaveExecutor.score_phase` drives it sequentially (dispatch every
    wave, drain, finish) and is behavior- and record-order-identical to
    the pre-refactor closed loop. The serve/ appraisal server drives
    SEVERAL PhaseRuns at once: while one session's dispatched wave is in
    flight (its `pending` not yet blocked), the server dispatches another
    session's wave — extending the PR 1 intra-phase double buffer to
    inter-session overlap without touching numerics (each run's keys,
    masks, and record order are exactly the sequential ones).

      dispatch(wi)  build + share wave wi, run the forward under the
                    per-wave ledger/tape scopes, then block the PREVIOUS
                    pending wave (double-buffer discipline)
      drain()       block the tail pending wave
      finish()      concat scores, reconcile + replay the wire tape,
                    return (AShare, PhaseReport)
    """

    def __init__(self, cfg: ExecConfig, key, pp, arch_cfg: ArchConfig,
                 tokens, spec: ProxySpec, variant=FULL_VARIANT,
                 outer: Ledger | None = None):
        if cfg.ring.bits >= 64 and jax.default_backend() == "tpu":
            raise ValueError(
                "RING64 cannot run on a TPU: XLA has no int64 dot lowering "
                "there (dot(s64, s64) is UNIMPLEMENTED). Use RING32, the "
                "TPU ring; RING64 is the CPU oracle.")
        self.cfg = cfg
        self.ring = ring = cfg.ring
        self.key = key
        self.pp = pp
        self.arch_cfg = arch_cfg
        self.spec = spec
        self.variant = variant
        B, W = cfg.batch, max(1, cfg.wave)
        self.B, self.W = B, W
        self.n = n = int(tokens.shape[0])
        self.seq = seq = int(tokens.shape[1])
        self.n_batches = n_batches = -(-n // B)
        self.n_waves = -(-n_batches // W)
        tok = np.asarray(tokens)
        full = n_batches * B
        if full > n:                                   # wrap-pad the tail,
            reps = -(-full // n)                       # tiling if B > n
            tok = np.concatenate([tok] * reps)[:full]
        self.tok = tok

        self.proto = proto = cfg.protocol
        self.n_parties = protocols.get(proto).n_parties

        # device mesh: "host" realizes party -> pod / wave -> data via
        # NamedSharding device_put (GSPMD inserts the cross-party
        # collectives); "shardmap" splits wave lanes over the data axis
        # with the party axis replicated per device (shard_map bodies
        # index party components explicitly, without collectives)
        self.rules = None
        if cfg.mesh == "host":
            self.rules = sharding.party_wave_rules(self.n_parties)
        elif cfg.mesh == "shardmap":
            self.rules = sharding.party_wave_rules(1, max_data=W)
        rules = self.rules
        self.dsize = sharding.data_axis_size(rules) if rules is not None else 1
        self.dev = DeviceReport(
            placement=cfg.mesh,
            n_devices=(int(rules.mesh.devices.size) if rules is not None
                       else 1),
            mesh_axes=(dict(rules.mesh.shape) if rules is not None else {}))

        # record into the ambient ledger at CONSTRUCTION time — a server
        # builds each run under its session's ledger scope (or passes
        # `outer` explicitly) and the records land per-session even when
        # dispatches interleave
        self.outer = comm.get_ledger() if outer is None else outer
        self.phase_led = Ledger()
        # --wire: capture every executed flight's actual messages; the
        # tape is sized by the WIRE party count (spdz2pc stacks 4 share
        # rows but runs 2 parties)
        self.tape = (comm.WireTape(protocols.get(proto).n_wire_parties)
                     if cfg.wire != "none" else None)
        self.scale = jnp.asarray(arch_cfg.d_model ** 0.5, jnp.float32)
        from repro.kernels import ops as kops
        self._kops = kops
        self.results: list[jax.Array] = []
        self.pending: jax.Array | None = None
        self.pending_wi = -1
        self.run_id = next(_RUN_IDS)

        with self._ctx(), span("phase.setup", run=self.run_id):
            self.pp_sh = proxy_mod.share_proxy(
                jax.random.fold_in(key, 1), pp, ring, proto)
            self.batch_keys = jax.random.split(
                jax.random.fold_in(key, 2), n_batches)
            # per-batch op-stream reference: the zero-FLOP eval_shape
            # probe (fused exactly like the executed forwards below),
            # memoized on the probe geometry — repeated phases of one
            # schedule reuse it
            self.per_batch = cached_probe(
                arch_cfg, spec, batch=B, seq=seq,
                classes=int(pp["cls_head"].shape[-1]), ring=ring,
                protocol=proto, fused=cfg.fuse, variant=variant)
            if cfg.mesh == "host":
                # weights resident once per phase: each party's share
                # components on its pod slice, value dims replicated
                self.pp_sh = sharding.place_party_tree(self.pp_sh)
        # the kernel-path, dealer, opening and reduction witnesses count
        # this phase's forwards only, not the probe above (a cold probe
        # traces every combine, draw and opening too)
        self.smm0 = kops.smm_stats()
        self.dealer0 = additive2pc.dealer_stats()
        self.open0 = additive2pc.open_stats()
        self.reduce0 = mops.reduce_stats()
        self.t0 = time.perf_counter()

    def _ctx(self):
        """The ambient scopes every step runs under — re-entered per
        call so interleaved runs (serve) never leak scopes into each
        other: x64 for RING64 arithmetic, sharding rules for the mesh."""
        stack = contextlib.ExitStack()
        if self.cfg.ring.bits >= 64:
            stack.enter_context(x64_scope())
        if self.rules is not None:
            stack.enter_context(sharding.rules_scope(self.rules))
        return stack

    def lanes(self, wi: int) -> int:
        b0, b1 = wi * self.W, min((wi + 1) * self.W, self.n_batches)
        return b1 - b0

    def _fwd(self, sh, k, pp_sh):
        cfg = self.cfg
        eng = MPCEngine(ring=self.ring, protocol=self.proto,
                        combine_impl=cfg.combine).with_key(k)
        with fusion.flight_scope(enabled=cfg.fuse):
            return proxy_entropy(eng, pp_sh, self.arch_cfg,
                                 AShare(sh, self.ring, self.proto),
                                 self.spec, self.variant).sh

    def dispatch(self, wi: int) -> None:
        """Run wave `wi` and leave it in flight (cfg.overlap) — blocking
        the previously pending wave only after this one is dispatched,
        so its wire time hides behind this wave's local compute."""
        cfg = self.cfg
        B, W, seq = self.B, self.W, self.seq
        rules, dsize = self.rules, self.dsize
        run = self.run_id
        with self._ctx():
            b0, b1 = wi * W, min((wi + 1) * W, self.n_batches)
            lanes = b1 - b0
            with span("wave.input", run=run, wave=wi):
                wave_tok = jnp.asarray(
                    self.tok[b0 * B:b1 * B]).reshape(lanes, B, seq)
                x = jnp.take(self.pp["embed"], wave_tok, axis=0) * self.scale
                x_sh = share(jax.random.fold_in(self.key, 100 + wi),
                             x.astype(jnp.float32), self.ring, self.proto)
                # party axis -> pod, wave axis -> data: a real device_put
                # on a mesh; without one, the legacy no-op annotation
                if rules is not None:
                    sh = sharding.place(x_sh.sh, "pod", "wave", "batch",
                                        None, None)
                else:
                    sh = sharding.shard(x_sh.sh, "pod", "wave", "batch",
                                        None, None)
            keys = self.batch_keys[b0:b1]
            used = 1

            # start_s and dispatch_s are taken at the edges of this span:
            # dispatch_s - start_s is its duration, on the host clock
            with span("wave.forward", run=run, wave=wi), \
                    comm.ledger_scope() as wave_led, \
                    comm.wire_tape_scope(self.tape):
                w_start = time.perf_counter() - self.t0
                if cfg.coalesce:
                    vf = jax.vmap(self._fwd, in_axes=(1, 0, None),
                                  out_axes=1)
                    if cfg.mesh == "shardmap" and dsize > 1 \
                            and lanes % dsize == 0:
                        # one fresh jit per wave: the re-trace is what
                        # fires this wave's comm.record side effects
                        # (a cached trace would silently skip them).
                        # The weights are an argument, replicated: as
                        # closure constants they would be compiled into
                        # the program
                        in_sh = P(*([None, "data"]
                                    + [None] * (sh.ndim - 2)))
                        vf = jax.jit(jax.shard_map(
                            vf, mesh=rules.mesh,
                            in_specs=(in_sh, P("data"), P()),
                            out_specs=P(None, "data", None),
                            check_vma=False))
                        used = dsize
                    elif rules is not None:
                        used = len(sh.sharding.device_set)
                    with comm.wave_scope(lanes):
                        ent = vf(sh, keys, self.pp_sh)
                else:
                    if rules is not None:
                        used = len(sh.sharding.device_set)
                    ent = jnp.stack([self._fwd(sh[:, li], keys[li],
                                               self.pp_sh)
                                     for li in range(lanes)], axis=1)
                self.phase_led.records.extend(wave_led.records)
                if self.outer is not None:
                    self.outer.records.extend(wave_led.records)

                ent = ent.reshape(self.n_parties, lanes * B)
                self.dev.waves.append(WaveTiming(
                    wave=wi, lanes=lanes, devices_used=used,
                    start_s=w_start,
                    dispatch_s=time.perf_counter() - self.t0))
            # double buffer: block on wave i-1 only after dispatching
            # i, so its wire time overlaps this wave's local compute
            if self.pending is not None:
                self._wait(self.pending, self.pending_wi)
                self.pending = None
            if cfg.overlap:
                self.pending, self.pending_wi = ent, wi
            else:
                self._wait(ent, wi)
            self.results.append(ent)

    def _wait(self, ent: jax.Array, wi: int) -> None:
        """Block on wave `wi`'s result and stamp its ready time."""
        with span("wave.wait", run=self.run_id, wave=wi):
            jax.block_until_ready(ent)
            self.dev.waves[wi].ready_s = time.perf_counter() - self.t0

    def drain(self) -> None:
        """Block the tail pending wave (the loop's final barrier)."""
        if self.pending is not None:
            with self._ctx():
                self._wait(self.pending, self.pending_wi)
            self.pending = None

    def finish(self) -> tuple[AShare, PhaseReport]:
        """Concatenate scores, reconcile/replay the wire tape, and seal
        the PhaseReport. Call after every wave dispatched + drain()."""
        with span("phase.finish", run=self.run_id):
            return self._finish()

    def _finish(self) -> tuple[AShare, PhaseReport]:
        cfg = self.cfg
        with self._ctx():
            out = jnp.concatenate(self.results, axis=1)[:, :self.n]
        wall_s = time.perf_counter() - self.t0
        smm1 = self._kops.smm_stats()
        dev = self.dev
        dev.combine_kernel = smm1["kernel"] - self.smm0["kernel"]
        dev.combine_ref = smm1["ref"] - self.smm0["ref"]
        dev.combine_padded = smm1["padded"] - self.smm0["padded"]
        dealer1 = additive2pc.dealer_stats()
        dev.dealer_draws = dealer1["draws"] - self.dealer0["draws"]
        dev.dealer_traces = dealer1["traces"] - self.dealer0["traces"]
        open1 = additive2pc.open_stats()
        dev.open_calls = open1["calls"] - self.open0["calls"]
        dev.open_traces = open1["traces"] - self.open0["traces"]
        reduce1 = mops.reduce_stats()
        dev.reduce_presum = reduce1["presum"] - self.reduce0["presum"]
        dev.reduce_odd_recip = (reduce1["odd_recip"]
                                - self.reduce0["odd_recip"])
        wire_rep = None
        if self.tape is not None:
            # replay the captured flight plan as real parties: reconcile
            # record-for-record against the phase ledger, then measure
            from repro import net
            net.reconcile(self.phase_led, self.tape)
            fault_plan = None
            if cfg.chaos_seed is not None:
                from repro.net import faults
                fault_plan = faults.FaultPlan.from_tape(
                    cfg.chaos_seed, self.tape,
                    crash_at_boundary=cfg.degraded)
            wire_rep = net.PartyRuntime(
                self.tape, mode=cfg.wire,
                profile=(comm.PROFILES[cfg.net] if cfg.wire == "socket"
                         else None),
                fault_plan=fault_plan,
                recover=fault_plan is not None and not cfg.degraded,
                degraded=cfg.degraded).execute()
        rep = PhaseReport(
            ledger=self.phase_led, per_batch=self.per_batch,
            n_batches=self.n_batches, n_waves=self.n_waves, wall_s=wall_s,
            sched=cfg.sched(), ring=self.ring, protocol=self.proto,
            fused=cfg.fuse, wire=wire_rep, device=dev)
        return AShare(out, self.ring, self.proto), rep


def run_variants(key, pp, arch_cfg: ArchConfig, tokens, spec: ProxySpec,
                 *, batch: int, wave: int,
                 flops_per_s: float = 10e12,
                 fuse: bool | None = None,
                 protocol: str = "2pc") -> dict[str, "PhaseReport"]:
    """Fig-7's four (coalesce, overlap) points, executed on one pool.

    Returns name -> PhaseReport; every variant is checked for exact
    ledger agreement with the makespan inputs, and all variants produce
    bitwise-identical scores (the schedule moves flights, not values —
    and the flight batcher — on by default, `fuse=None` follows
    ExecConfig — compresses rounds without changing a share either).
    """
    reports = {}
    ref = None
    fuse_kw = {} if fuse is None else {"fuse": fuse}
    for name, (co, ov) in iosched.FIG7_VARIANTS.items():
        ex = WaveExecutor(ExecConfig(wave=wave, coalesce=co, overlap=ov,
                                     batch=batch, flops_per_s=flops_per_s,
                                     protocol=protocol, **fuse_kw))
        ent = ex.score_phase(key, pp, arch_cfg, tokens, spec)
        rep = ex.reports[-1]
        if not rep.agrees():
            raise AssertionError(
                f"executor ledger for {name} diverges from makespan inputs")
        if ref is None:
            ref = np.asarray(ent.sh)
        elif not np.array_equal(ref, np.asarray(ent.sh)):
            raise AssertionError(f"variant {name} changed scores")
        reports[name] = rep
    return reports
