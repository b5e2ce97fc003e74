"""The one traffic generator: reads a traffic file and drives the program
through its public entry point WaveExecutor.score_phase, one unit of
work at a time. A unit is one request: a fresh pool of the traffic's
size scored at the traffic's phase of the schedule, with the proxy made
once at set-up.

Closed loop: the next unit starts when the last has finished. Every
pool, key and weight comes from the seed; unit u of a run always gets
the same sizes, so seeds differ only in values.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import pools, reference
from bench.harness import Phase


def sub_seed(seed: int, *path: int) -> int:
    """A 31-bit seed for one unit, from the run's seed (any size)."""
    ss = np.random.SeedSequence([seed % 2 ** 63, *path])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def gap_stats(d: np.ndarray) -> dict:
    """The largest and the root-mean-square gap over every answer."""
    return {"max": float(np.max(d)),
            "rms": float(np.sqrt(np.mean(np.square(d))))}


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


class Score:
    """A stream of scoring requests through WaveExecutor.score_phase."""

    def __init__(self, cell, seed: int, combine: str):
        import jax
        from repro.configs.base import ArchConfig
        from repro.core.proxy import ProxySpec
        from repro.mpc.ring import RING32

        c, t = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.c, self.t = c, t
        self.arch = ArchConfig(
            name=c["name"], family="dense",
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_attention_heads"], d_head=c["head_dim"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            norm_type="layernorm", act="gelu", rope_theta=1e4)
        mpc = c["mpc"]
        if (mpc["bits"], mpc["frac_bits"]) != (RING32.bits, RING32.frac_bits):
            raise ValueError(f"ring {mpc} is not RING32")
        self.ring = RING32
        self.spec = ProxySpec(*c["schedule"][t["phase"]])
        self.batch = c["selection"]["score_batch"]
        self.combine = combine
        self.jax = jax
        self.attempted = 0
        self.failed = 0
        self.candidates = 0
        self.window_phases: list[Phase] = []

    def exec_config(self):
        from repro.core.executor import ExecConfig
        m = self.c["mpc"]
        return ExecConfig(ring=self.ring, protocol=m["protocol"],
                          wave=m["wave"], batch=self.batch, fuse=m["fuse"],
                          overlap=m["overlap"], combine=self.combine)

    def pool(self, seed: int):
        t = self.t
        return pools.classification_pool(
            seed, n_pool=t["pool"], seq=self.c["seq_len"],
            vocab=self.c["vocab_size"], n_classes=self.c["n_classes"],
            imbalance=t["imbalance"], signal=t["signal"],
            redundancy=t["redundancy"])[0]

    def setup(self, warm: bool = True):
        """The proxy, from the program's own generator
        (core/proxy.random_proxy) in one jitted call on the device, and
        one whole request to warm every program of the cell: the phase's
        key split and the concatenation of its waves take the pool's
        shape, so one wave would leave them to compile in the window."""
        from repro.core.executor import WaveExecutor
        from repro.core.proxy import random_proxy
        jax = self.jax
        self.proxy = jax.jit(random_proxy, static_argnums=(1, 2, 3, 4))(
            jax.random.key(sub_seed(self.seed, 0)), self.arch, self.spec,
            self.c["seq_len"], self.c["n_classes"])
        self.ex = WaveExecutor(self.exec_config())
        self.done: list[tuple] = []
        if warm:
            self.unit(-1)
            self.done.clear()
            self.window_phases.clear()
            self.attempted = self.candidates = 0

    def unit(self, i: int):
        jax = self.jax
        tokens = self.pool(sub_seed(self.seed, 2, i + 1))
        self.attempted += 1
        ent = self.ex.score_phase(jax.random.key(sub_seed(self.seed, 3,
                                                          i + 1)),
                                  self.proxy, self.arch, tokens, self.spec)
        rep = self.ex.reports[-1]
        self.done.append((tokens, np.asarray(ent.sh), rep))
        self.candidates += len(tokens)
        self.window_phases.append(Phase(
            self.spec.n_layers, self.spec.n_heads, self.spec.mlp_dim,
            len(tokens), self.batch, rep.n_batches,
            [(w.start_s, w.dispatch_s, w.ready_s)
             for w in rep.device.waves]))

    def release(self):
        self.ex = None

    def _ref(self, tokens, precision="float32"):
        return reference.score(
            self.proxy, tokens, n_layers=self.spec.n_layers,
            n_heads=self.spec.n_heads,
            n_kv_heads=self.c["num_attention_heads"],
            d_head=self.c["head_dim"], precision=precision)

    def _decode(self, sh) -> np.ndarray:
        m = self.c["mpc"]
        return reference.decode(sh, m["value_rows"], m["frac_bits"])

    def readings(self, precisions=("fx16", "bfloat16")) -> dict:
        """The gap statistics to the reference of the program's answers
        and of each control precision's, on the same answers."""
        d = {k: [] for k in ("program",) + precisions}
        for tokens, sh, _ in self.done:
            ref = self._ref(tokens)
            d["program"].append(np.abs(self._decode(sh) - ref))
            for p in precisions:
                d[p].append(np.abs(self._ref(tokens, p) - ref))
        return {k: gap_stats(np.concatenate(v)) for k, v in d.items()}

    def check(self) -> list[Check]:
        """Every score of every request in the window against the
        reference on the same proxy and candidates, by each gap statistic
        the cell's limits name (`gap.max`, `gap.rms`); ledger agreement."""
        limits = {k[len("gap."):]: v for k, v in self.cell.limits.items()
                  if k.startswith("gap.")}
        diffs, ledger, unfinished = [], 0, 0
        for tokens, sh, rep in self.done:
            dec, ref = self._decode(sh), self._ref(tokens)
            bad = dec.shape != ref.shape
            if bad:
                unfinished += 1
            else:
                diffs.append(np.abs(dec - ref))
                st = gap_stats(diffs[-1])
                bad = any(st[k] > v for k, v in limits.items())
            if not rep.agrees():
                ledger += 1
                bad = True
            self.failed += int(bad)
        st = (gap_stats(np.concatenate(diffs)) if diffs
              else dict.fromkeys(limits, 0.0))
        return ([Check(f"gap.{k}", st[k], v) for k, v in limits.items()]
                + [Check("ledger", ledger, 0),
                   Check("unfinished", unfinished, 0)])

