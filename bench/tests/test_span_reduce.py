"""The program-span reduction, and trace_reduce's idle-gap attribution
over both span families, on hand-made events with known answers."""
import pytest

from bench import span_reduce, trace_reduce

# one wave: input (with its share), a forward of two stages, a wait
SF = [
    (110, 40, "sf.wave.input"), (120, 20, "sf.mpc.share"),
    (150, 600, "sf.wave.forward"),
    (160, 300, "sf.stage.qkv"),
    (170, 10, "sf.mpc.key"), (200, 100, "sf.mpc.dealer"),
    (300, 50, "sf.mpc.open"), (350, 50, "sf.mpc.combine"),
    (460, 280, "sf.stage.softmax"),
    (500, 200, "sf.mpc.trunc"), (510, 50, "sf.mpc.dealer"),
    (560, 40, "sf.mpc.open"),
    (700, 30, "sf.mpc.cmp"), (710, 10, "sf.mpc.share"),
    (750, 50, "sf.wave.wait"),
]
EVENTS = {
    "spans": [(0, 1000, "bench.window"), (100, 800, "bench.dispatch")],
    "devices": [{"plane": "/device:TPU:0",
                 "ops": [(50, 20, "a"), (300, 10, "b"), (720, 5, "c"),
                         (950, 20, "d")],
                 "modules": []}],
}


def test_host_spans_counts_inclusive_and_self():
    hs = span_reduce.host_spans(SF, 0, 1000)
    assert hs["sf.mpc.dealer"] == {"count": 2, "self_s": pytest.approx(150e-9),
                                   "incl_s": pytest.approx(150e-9)}
    assert hs["sf.mpc.share"]["count"] == 2
    assert hs["sf.mpc.share"]["incl_s"] == pytest.approx(30e-9)
    # self: each interval less the spans directly inside it
    assert hs["sf.mpc.trunc"]["self_s"] == pytest.approx(110e-9)
    assert hs["sf.stage.qkv"]["self_s"] == pytest.approx(90e-9)
    assert hs["sf.stage.softmax"]["self_s"] == pytest.approx(50e-9)
    assert hs["sf.mpc.cmp"]["self_s"] == pytest.approx(20e-9)
    assert hs["sf.wave.forward"]["self_s"] == pytest.approx(20e-9)
    assert hs["sf.wave.forward"]["incl_s"] == pytest.approx(600e-9)
    # a window that ends before the wait leaves it out
    assert "sf.wave.wait" not in span_reduce.host_spans(SF, 0, 760)


def test_wave_split_partitions_the_forward():
    ((fwd, dealer, online, local),) = span_reduce.wave_split(SF)
    assert fwd == pytest.approx(600e-9)
    assert dealer == pytest.approx(150e-9)
    # open 50 + combine 50 + trunc 200 + cmp 30 (share inside it), less
    # the dealer's 50 inside the trunc
    assert online == pytest.approx(280e-9)
    # the forward less every mpc span but the key's
    assert local == pytest.approx(170e-9)
    m = span_reduce.wave_metrics(SF, 0, 1000)
    assert m["wave_input_ms"] == pytest.approx(40e-6)
    assert m["wave_wait_ms"] == pytest.approx(50e-6)
    assert (m["dealer_ms"], m["online_ms"], m["local_ms"]) == pytest.approx(
        (150e-6, 280e-6, 170e-6))
    assert span_reduce.wave_metrics(SF, 0, 100) is None


def test_idle_gaps_go_to_the_innermost_span_of_either_family():
    """span_table's attribution: trace_reduce.reduce over the harness's
    spans and the program's together."""
    merged = dict(EVENTS, spans=EVENTS["spans"] + SF)
    idle = dict(trace_reduce.reduce(merged, top=10 ** 6)["idle_gaps"])
    # gaps [0,50] and [970,1000]: no span; [70,300] (mid 185): qkv;
    # [310,720] (mid 515): the trunc's dealer; [725,950] (mid 837.5):
    # bench.dispatch, after the wait
    assert idle == pytest.approx({"(no span)": 80e-9,
                                  "sf.stage.qkv": 230e-9,
                                  "sf.mpc.dealer": 410e-9,
                                  "bench.dispatch": 225e-9})
    assert sum(idle.values()) == pytest.approx(945e-9)
