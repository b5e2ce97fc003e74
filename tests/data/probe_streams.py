"""Pinned per-batch op streams of the TraceEngine probe, and their regenerator.

`probe_streams.txt` holds, for every geometry the tests price, the
record stream `TraceEngine.probe` returns for one batch: one line per
record, `case op rounds nbytes numel flops tag`. The tests compare the
probe (and an executed phase's `PhaseReport.per_batch`) with it record
for record.

A change that moves the op stream on purpose regenerates the file:

    python tests/data/probe_streams.py

and the file's diff lists the records that moved, for review.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PATH = os.path.join(HERE, "probe_streams.txt")

PROTOCOLS = ("2pc", "3pc", "spdz2pc", "aby3trunc")
RINGS = ("ring64", "ring32")
MODES = ("eager", "fused")


@dataclasses.dataclass(frozen=True)
class Case:
    geom: str
    protocol: str
    ring: str
    mode: str
    batch: int

    @property
    def id(self) -> str:
        return (f"{self.geom}.{self.protocol}.{self.ring}.{self.mode}"
                f".b{self.batch}")


def _cases() -> tuple[Case, ...]:
    tiny = [Case("tiny", p, r, m, 6)
            for p in PROTOCOLS for r in RINGS for m in MODES]
    # the geometries the executor tests run (batch 8)
    executed = [Case("tiny", "2pc", "ring64", "eager", 8),
                Case("tiny", "2pc", "ring32", "eager", 8),
                Case("tiny", "2pc", "ring32", "fused", 8)]
    executed += [Case("tiny", p, "ring64", m, 8)
                 for p in ("3pc", "spdz2pc", "aby3trunc") for m in MODES]
    # BERT-base width, the <3,12,16> phase
    wide = [Case("wide", p, "ring32", m, 2) for p in PROTOCOLS
            for m in MODES]
    deep = [Case("deep", p, "ring32", "eager", 6) for p in ("2pc", "3pc")]
    return tuple(tiny + executed + wide + deep)


CASES = _cases()
PROBE_CASES = tuple(c for c in CASES if not (c.geom == "tiny"
                                             and c.batch == 8))


def geometry(name: str):
    """(cfg, spec, seq, classes) of a named geometry."""
    from repro.configs.paper_targets import TINY_TARGET
    from repro.core.proxy import ProxySpec

    if name == "wide":
        cfg = dataclasses.replace(TINY_TARGET, vocab_size=512, n_layers=12,
                                  d_model=768, n_heads=12, n_kv_heads=12,
                                  d_head=64, d_ff=3072)
        return cfg, ProxySpec(3, 12, 16), 128, 2
    layers = {"tiny": 1, "deep": 2}[name]
    cfg = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=layers,
                              d_model=32, n_heads=2, n_kv_heads=2,
                              d_head=16, d_ff=64)
    return cfg, ProxySpec(layers, 2, 4), 8, 3


def probe(case: Case):
    """The probe's per-batch Ledger at `case`."""
    from repro.engine import TraceEngine, abstract_shares
    from repro.mpc.ring import RING32, RING64

    ring = {"ring64": RING64, "ring32": RING32}[case.ring]
    cfg, spec, seq, classes = geometry(case.geom)
    pp_sh = abstract_shares(cfg, spec, seq, classes, ring, case.protocol)
    return TraceEngine(ring, protocol=case.protocol).probe(
        pp_sh, cfg, spec, (case.batch, seq, cfg.d_model),
        fused=case.mode == "fused")


def lines(case_id: str, ledger) -> list[str]:
    """One text line per record of `ledger`."""
    return [f"{case_id} {r.op} {r.rounds} {r.nbytes} {r.numel} {r.flops} "
            f"{r.tag}" for r in ledger.records]


@functools.lru_cache(maxsize=1)
def pinned() -> dict[str, list[str]]:
    """{case id: its pinned lines}, read from the file."""
    out: dict[str, list[str]] = {}
    with open(PATH) as f:
        for line in f:
            line = line.rstrip("\n")
            out.setdefault(line.split(" ", 1)[0], []).append(line)
    return out


def assert_matches(case: Case, ledger) -> None:
    """`ledger` is the pinned stream of `case`, record for record."""
    got, want = lines(case.id, ledger), pinned()[case.id]
    i = next((i for i, (g, w) in enumerate(zip(got, want)) if g != w),
             min(len(got), len(want)))
    assert got == want, (
        f"{case.id}: {len(got)} records against {len(want)} pinned; "
        f"first difference at record {i}: {got[i:i + 1]} against "
        f"{want[i:i + 1]} (regenerate with tests/data/probe_streams.py "
        f"if the stream moved on purpose)")


def main() -> int:
    sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))
    out = []
    for case in CASES:
        out += lines(case.id, probe(case))
    with open(PATH, "w") as f:
        f.write("\n".join(out) + "\n")
    print(f"{len(CASES)} cases, {len(out)} records -> {PATH}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
