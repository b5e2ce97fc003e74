"""The program's `sf.*` spans, read back from a real profiler trace.

One tiny RING32 2PC phase (2 waves of 2 batches) is scored under
`jax.profiler` on the CPU, after a warm-up phase that fills the probe
cache and compiles every op, and its trace is read with
bench/span_reduce.py; each backend's primitives are traced on their
own. Every profiler test lives in this one file: a process holds one
profiler session at a time, and the tier-1 run (`-p xdist --dist
loadfile`) keeps a file on one worker.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import span_reduce, trace_reduce
from repro.configs.paper_targets import TINY_TARGET
from repro.core import proxy as proxy_mod
from repro.core.executor import ExecConfig, WaveExecutor
from repro.core.proxy import ProxySpec
from repro.mpc import ops as mops, sharing
from repro.mpc.ring import RING32
from repro.spans import span

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=96, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, WAVE, CLASSES = 8, 8, 2, 2
POOL = 32                        # 4 batches -> 2 waves of 2

SPAN_NAMES = (
    ["sf.phase.setup", "sf.phase.finish", "sf.wave.input",
     "sf.wave.forward", "sf.wave.wait"]
    + [f"sf.stage.{s}" for s in ("ln_stats", "ln_inv", "ln_affine", "qkv",
                                 "scores", "softmax", "av", "wo", "head",
                                 "entropy")]
    + [f"sf.mpc.{s}" for s in ("dealer", "open", "combine", "trunc", "cmp",
                               "share", "key")])


def _within(s, outer) -> bool:
    return outer[0] <= s[0] and s[0] + s[1] <= outer[0] + outer[1]


def _trace_phase(out, protocol="2pc"):
    """(spans, PhaseReport, n_waves) of one traced phase, after a
    warm-up phase."""
    key = jax.random.key(0)
    pp = proxy_mod.random_proxy(key, CFG, SPEC, seq_len=SEQ,
                                n_classes=CLASSES)
    pool = np.random.default_rng(0).integers(0, CFG.vocab_size, (POOL, SEQ))
    ex = WaveExecutor(ExecConfig(wave=WAVE, batch=BATCH, ring=RING32,
                                 protocol=protocol))
    ex.score_phase(jax.random.fold_in(key, 1), pp, CFG, pool, SPEC)
    jax.profiler.start_trace(str(out))
    try:
        ent = ex.score_phase(jax.random.fold_in(key, 2), pp, CFG, pool, SPEC)
        jax.block_until_ready(ent.sh)
    finally:
        jax.profiler.stop_trace()
    spans = span_reduce.load(trace_reduce.latest_xplane(str(out)))
    return spans, ex.reports[-1], ex.reports[-1].n_waves


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("trace")


@pytest.fixture(scope="module")
def traced(trace_dir):
    return _trace_phase(trace_dir)


def _named(spans, name):
    return [s for s in spans if s[2] == name]


def _in_forward(spans, prefix):
    fwd = _named(spans, span_reduce.FORWARD)
    return [s for s in spans if s[2].startswith(prefix)
            and any(_within(s, f) for f in fwd)]


def test_every_span_appears(traced):
    spans, _, _ = traced
    seen = {s[2] for s in spans}
    assert set(SPAN_NAMES) <= seen, sorted(set(SPAN_NAMES) - seen)


def test_one_forward_per_wave(traced):
    spans, rep, n_waves = traced
    assert n_waves == 2
    assert len(_named(spans, span_reduce.FORWARD)) == n_waves
    assert len(_named(spans, "sf.wave.input")) == n_waves
    assert len(_named(spans, "sf.wave.wait")) == n_waves
    assert len(_named(spans, "sf.phase.setup")) == 1


def test_stages_nest_in_forward_and_mpc_in_stages(traced):
    spans, _, _ = traced
    fwd = _named(spans, span_reduce.FORWARD)
    stages = [s for s in spans if s[2].startswith("sf.stage.")]
    assert stages and all(any(_within(s, f) for f in fwd) for s in stages)
    mpc = _in_forward(spans, "sf.mpc.")
    assert mpc and all(any(_within(s, st) for st in stages) for s in mpc)


def test_each_beaver_op_and_trunc_draws_and_opens_once(traced):
    spans, rep, _ = traced
    n = {name: len(_in_forward(spans, f"sf.mpc.{name}"))
         for name in ("dealer", "open", "combine", "trunc")}
    assert n["trunc"] > 0 and n["combine"] > 0
    assert n["dealer"] == n["combine"] + n["trunc"]
    assert n["open"] == n["combine"] + n["trunc"]
    dev = rep.device
    assert dev.combine_kernel + dev.combine_ref > 0
    assert n["combine"] >= dev.combine_kernel + dev.combine_ref


def test_forward_splits_into_dealer_online_local(traced):
    spans, _, n_waves = traced
    split = span_reduce.wave_split(spans)
    assert len(split) == n_waves
    for fwd, dealer, online, local in split:
        assert min(dealer, online, local) >= 0
        assert dealer + online + local == pytest.approx(fwd, rel=1e-9)


def test_wave_stamps_share_the_forward_span(traced):
    """dispatch_s - start_s and the sf.wave.forward span measure one
    interval: both are taken at the span's edges."""
    spans, rep, _ = traced
    fwd = sorted(_named(spans, span_reduce.FORWARD))
    for w, f in zip(rep.device.waves, fwd):
        assert w.dispatch_s - w.start_s == pytest.approx(f[1] / 1e9,
                                                         rel=0.02)
        assert w.ready_s >= w.dispatch_s



@pytest.mark.parametrize("protocol", ["2pc", "spdz2pc", "3pc", "aby3trunc"])
def test_every_backend_spans_its_primitives(protocol, tmp_path):
    """A matmul, a multiply and a forced truncation of each backend,
    inside a stand-in forward span: SPDZ's MAC'd dealer and openings are
    spanned like 2PC's, and the dealer-free 3-party backends read no
    dealer time, their resharing multiplies counted as online."""
    key = jax.random.key(3)
    x = sharing.share(key, jnp.full((4, 8), 0.5), RING32, protocol)
    w = sharing.share(jax.random.fold_in(key, 1), jnp.full((8, 4), 0.25),
                      RING32, protocol)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("wave.forward"):
            y = mops.matmul(x, w, jax.random.fold_in(key, 2))
            y = mops.mul(y, y, jax.random.fold_in(key, 3))
            y = mops.force(y, jax.random.fold_in(key, 4))
            jax.block_until_ready(y.sh)
    finally:
        jax.profiler.stop_trace()
    spans = span_reduce.load(trace_reduce.latest_xplane(str(tmp_path)))
    n = {name: len(_in_forward(spans, f"sf.mpc.{name}"))
         for name in ("dealer", "open", "combine", "trunc", "reshare")}
    assert n["trunc"] > 0
    if protocol in ("2pc", "spdz2pc"):
        assert n["combine"] == 2 and n["reshare"] == 0
        assert n["dealer"] == n["combine"] + n["trunc"]
        assert n["open"] == n["combine"] + n["trunc"]
    else:
        assert n["reshare"] == 2 and n["dealer"] == n["combine"] == 0
        assert n["open"] == (n["trunc"] if protocol == "aby3trunc" else 0)
    ((fwd, dealer, online, local),) = span_reduce.wave_split(spans)
    assert min(dealer, online, local) >= 0 and online > 0
    assert (dealer > 0) == (protocol in ("2pc", "spdz2pc"))
    assert dealer + online + local == pytest.approx(fwd, rel=1e-9)


def _site_spans(path, name, site):
    """(start_ns, dur_ns) of every host event `name` whose meta `site`
    is `site` (bench/span_reduce.load keeps names only)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.duration_ns) for e in line.events
                           if e.name == name
                           and (("site", site) in list(e.stats)))
    return out


@pytest.mark.parametrize("protocol", ["2pc", "spdz2pc", "3pc", "aby3trunc"])
def test_presum_trunc_spans_as_counted(protocol, tmp_path):
    """The variance of a 96-wide product inside a stand-in LayerNorm
    stage: its pre-sum truncation is one `site="presum"` trunc span
    there, as ops.reduce_stats counts it, and the split still sums."""
    key = jax.random.key(4)
    x = sharing.share(key, 0.5 * jax.random.normal(key, (4, 96)), RING32,
                      protocol)
    sq = mops.mul(x, x, jax.random.fold_in(key, 1))
    s0 = mops.reduce_stats()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("wave.forward"), span("stage.ln_stats"):
            var = mops.mean(sq, axis=-1, key=jax.random.fold_in(key, 2))
            jax.block_until_ready(var.sh)
    finally:
        jax.profiler.stop_trace()
    s1 = mops.reduce_stats()
    path = trace_reduce.latest_xplane(str(tmp_path))
    presum = _site_spans(path, "sf.mpc.trunc", "presum")
    spans = span_reduce.load(path)
    (ln,) = _named(spans, "sf.stage.ln_stats")
    assert s1["presum"] - s0["presum"] == 1
    assert len(presum) == 1 and _within(presum[0], ln)
    ((fwd, dealer, online, local),) = span_reduce.wave_split(spans)
    assert (dealer > 0) == (protocol in ("2pc", "spdz2pc"))
    assert dealer + online + local == pytest.approx(fwd, rel=1e-9)


def test_phase_presum_truncs_sit_in_ln_stats(traced, trace_dir):
    """The traced phase at hidden 96: two pre-sum truncations a wave, the
    variance's inside `sf.stage.ln_stats` and the pool's (8 positions of
    the 2f residual) inside `sf.stage.head`; the canonical layer input
    needs none. As many as the phase's DeviceReport counts; both
    statistics encode 1/3, the pool's 1/8 folds."""
    spans, rep, n_waves = traced
    presum = _site_spans(trace_reduce.latest_xplane(str(trace_dir)),
                         "sf.mpc.trunc", "presum")
    ln = _in_forward(spans, "sf.stage.ln_stats")
    head = _in_forward(spans, "sf.stage.head")
    assert len(ln) == len(head) == n_waves == 2
    assert (rep.device.reduce_presum, rep.device.reduce_odd_recip) == (4, 4)
    in_ln = [p for p in presum if any(_within(p, s) for s in ln)]
    in_head = [p for p in presum if any(_within(p, s) for s in head)]
    assert len(in_ln) == len(in_head) == n_waves
    assert len(in_ln) + len(in_head) == rep.device.reduce_presum
    d = rep.device.as_dict()
    assert (d["reduce_presum"], d["reduce_odd_recip"]) == (4, 4)
