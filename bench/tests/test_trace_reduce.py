"""The trace-to-metric reduction, on hand-made events with known answers
and on a small trace recorded on a TPU v5e."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data" / "trace_small.json.gz"


def test_union_gaps_and_attribution():
    ev = {
        "spans": [(0, 100, "bench.window"), (0, 50, "bench.plan"),
                  (60, 40, "bench.dispatch"), (10, 10, "bench.quickselect")],
        "devices": [{"plane": "/device:TPU:0",
                     "ops": [(20, 10, "fusion.1"), (25, 10, "fusion.2"),
                             (70, 20, "custom-call.3"), (95, 30, "copy.4")],
                     "modules": [(20, 15, "jit__combine(7)"),
                                 (70, 20, "jit__combine(7)"),
                                 (95, 30, "jit_add(2)")]}],
    }
    tr = trace_reduce.reduce(ev)
    # busy: [20, 35] + [70, 90] + [95, 100] inside the window
    assert tr["busy_s"] == pytest.approx(40e-9)
    assert tr["window_s"] == pytest.approx(100e-9)
    assert tr["module_count"] == {"jit__combine": 2, "jit_add": 1}
    assert tr["module_s"]["jit__combine"] == pytest.approx(35e-9)
    gaps = dict(tr["idle_gaps"])
    # gaps [0,20] (mid 10: quickselect, the innermost), [35,70] (mid 52.5:
    # no span), [90,95] (mid 92.5: dispatch)
    assert gaps["bench.quickselect"] == pytest.approx(20e-9)
    assert gaps["(no span)"] == pytest.approx(35e-9)
    assert gaps["bench.dispatch"] == pytest.approx(5e-9)
    assert sum(gaps.values()) == pytest.approx(60e-9)


def test_no_window_or_no_device_reads_nothing():
    assert trace_reduce.reduce({"spans": [], "devices": []}) is None
    assert trace_reduce.reduce({"spans": [(0, 5, "bench.window")],
                                "devices": []}) is None


def test_recorded_trace_invariants():
    """Two waves of a small 2PC phase on a TPU v5e
    (bench/tests/record_trace.py)."""
    ev = json.loads(gzip.decompress(DATA.read_bytes()))
    tr = trace_reduce.reduce(ev)
    assert 0 < tr["busy_s"] <= tr["window_s"]
    idle = sum(v for _, v in trace_reduce.reduce(ev, top=10 ** 6)
               ["idle_gaps"])
    assert idle == pytest.approx(tr["window_s"] - tr["busy_s"], rel=1e-9)
    # 2 waves x (q, k, v, o of one block + the class head)
    assert tr["module_count"]["jit__combine"] == 10
    assert tr["busy_s"] == pytest.approx(0.005380452)
