"""The TraceEngine probe is the one accountant of the executed op stream.

Its per-batch record streams are pinned, as data, in
tests/data/probe_streams.txt. Contracts:

  1. PINNED — `TraceEngine.probe` returns the pinned stream record for
     record (op, rounds, bytes, elements, flops, tag) at:
       tiny (d 32, 2 heads, seq 8, batch 6, 3 classes): 4 protocols x
         2 rings x eager/fused;
       hidden 768, RING32, <3,12,16>, seq 128, batch 2: 4 protocols x
         eager/fused (the LayerNorm reduction rule's pre-sum
         truncations included);
       two layers, RING32: 2pc and 3pc (the residual's align-down).
  2. COMPLETE — the file holds exactly the cases its regenerator lists.

A deliberate change to the op stream regenerates the file
(`python tests/data/probe_streams.py`); its diff is the review.
"""
import pytest

from data import probe_streams as streams


@pytest.mark.parametrize("case", streams.PROBE_CASES, ids=lambda c: c.id)
def test_probe_matches_pinned_stream(case):
    streams.assert_matches(case, streams.probe(case))


def test_file_pins_every_case():
    assert set(streams.pinned()) == {c.id for c in streams.CASES}
