"""The 2PC dealer's draws, compiled once per signature.

`mul_triple`, `matmul_triple` and `trunc_pair` run a module-level jitted
core (static in shape, ring and shift) under a thin wrapper that keeps
the offline record outside the jit. Contracts:
  1. BITWISE — each draw, vmapped over batched keys as the wave forward
     runs it, equals the same call under `jax.disable_jit()`.
  2. ONE RECORD PER DRAW — every call records its offline bytes, the
     same record as the eager draw, also under an abstract trace.
  3. CACHED — a shape's second draw traces nothing; a warm wave of a
     RING32 2PC phase draws from cached programs only.
"""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_targets import TINY_TARGET
from repro.core import proxy as proxy_mod
from repro.core.executor import ExecConfig, PhaseRun
from repro.core.proxy import ProxySpec
from repro.mpc import comm
from repro.mpc.protocols import additive2pc
from repro.mpc.protocols.additive2pc import (
    Additive2PC, dealer_stats, matmul_triple, mul_triple, reset_dealer_stats,
    trunc_pair)
from repro.mpc.ring import RING32, RING64, x64_scope
from repro.mpc.sharing import share

RINGS = {"ring32": RING32, "ring64": RING64}

# (name, draw(key, ring) -> tuple of Shares)
DRAWS = {
    "mul": lambda k, r: mul_triple(k, (3, 5), r),
    "matmul": lambda k, r: matmul_triple(k, (4, 6), (6, 3), r),
    "matmul_batched": lambda k, r: matmul_triple(k, (2, 4, 6), (6, 3), r),
    "trunc_default": lambda k, r: trunc_pair(k, (7,), r),
    "trunc_shift5": lambda k, r: trunc_pair(k, (7,), r, 5),
    "trunc_shift_f5": lambda k, r: trunc_pair(k, (2, 7), r,
                                              r.frac_bits + 5),
}


def _scope(ring):
    return x64_scope() if ring.bits >= 64 else contextlib.nullcontext()


def _keys(seed=0):
    return jax.random.split(jax.random.key(seed), 2)


def _vmapped(draw, ring):
    return jax.vmap(lambda k: tuple(s.sh for s in draw(k, ring)))


@pytest.mark.parametrize("ring_name", list(RINGS))
@pytest.mark.parametrize("draw_name", list(DRAWS))
def test_vmapped_draw_bitwise_equals_eager(draw_name, ring_name):
    ring = RINGS[ring_name]
    with _scope(ring):
        f = _vmapped(DRAWS[draw_name], ring)
        compiled = [np.asarray(x) for x in f(_keys())]
        with jax.disable_jit():
            eager = [np.asarray(x) for x in f(_keys())]
    assert len(compiled) == len(eager)
    for c, e in zip(compiled, eager):
        assert c.dtype == e.dtype == ring.dtype
        np.testing.assert_array_equal(c, e)
    # the two lanes draw from different keys
    assert not np.array_equal(compiled[0][0], compiled[0][1])


@pytest.mark.parametrize("ring_name", list(RINGS))
def test_broadcast_mul_bitwise_equals_eager(ring_name):
    """`Additive2PC.mul` draws its triple at the broadcast shape."""
    ring = RINGS[ring_name]
    be = Additive2PC()
    with _scope(ring):
        x = share(jax.random.key(1), jnp.linspace(-2, 2, 4).reshape(4, 1),
                  ring)
        y = share(jax.random.key(2), jnp.linspace(-1, 1, 6).reshape(1, 6),
                  ring)
        f = jax.vmap(lambda k: be.mul(x, y, k).sh)
        compiled = np.asarray(f(_keys(3)))
        with jax.disable_jit():
            eager = np.asarray(f(_keys(3)))
        np.testing.assert_array_equal(compiled, eager)
        assert compiled.shape == (2, 2, 4, 6)
        # the raw product carries 2f fractional bits
        got = ring.decode_at(compiled[0].sum(0), 2 * ring.frac_bits)
        want = np.outer(np.linspace(-2, 2, 4), np.linspace(-1, 1, 6))
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-2)


def _records(draw, ring, disable_jit=False):
    ctx = jax.disable_jit() if disable_jit else contextlib.nullcontext()
    with _scope(ring), ctx, comm.ledger_scope() as led:
        f = _vmapped(draw, ring)
        f(_keys())
        f(_keys(1))
    return [(r.op, r.rounds, r.nbytes, r.numel, r.tag) for r in led.records]


@pytest.mark.parametrize("ring_name", list(RINGS))
@pytest.mark.parametrize("draw_name", ["mul", "matmul_batched",
                                       "trunc_shift5"])
def test_one_offline_record_per_draw(draw_name, ring_name):
    ring = RINGS[ring_name]
    recs = _records(DRAWS[draw_name], ring)
    assert len(recs) == 2 and recs[0] == recs[1]
    assert recs[0][0].startswith("offline.") and recs[0][4] == "offline"
    assert recs == _records(DRAWS[draw_name], ring, disable_jit=True)


def test_offline_record_counts_every_shipped_element():
    with comm.ledger_scope() as led:
        matmul_triple(jax.random.key(0), (2, 4, 6), (6, 3), RING32)
        trunc_pair(jax.random.key(1), (7,), RING32)
    mm, tp = led.records
    assert mm.numel == 2 * 4 * 6 + 6 * 3 + 2 * 4 * 3
    assert mm.nbytes == 2 * RING32.elem_bytes * mm.numel
    assert tp.numel == 2 * 7


def test_records_fire_under_eval_shape():
    """The TraceEngine probe runs the draw abstractly: the core traces,
    the wrapper still records."""
    with comm.ledger_scope() as led:
        out = jax.eval_shape(
            lambda k: mul_triple(k, (5, 2), RING32)[2].sh, jax.random.key(0))
    assert out.shape == (2, 5, 2) and out.dtype == jnp.int32
    assert [r.op for r in led.records] == ["offline.mul_triple"]


@pytest.mark.parametrize("draw_name", ["mul", "matmul", "trunc_default"])
def test_second_draw_of_a_shape_traces_nothing(draw_name):
    f = _vmapped(DRAWS[draw_name], RING32)
    f(_keys())
    s0 = dealer_stats()
    f(_keys(1))
    s1 = dealer_stats()
    assert s1["traces"] == s0["traces"]
    assert s1["draws"] == s0["draws"] + 1


def test_reset_dealer_stats():
    mul_triple(jax.random.key(0), (2,), RING32)
    assert dealer_stats()["draws"] >= 1
    reset_dealer_stats()
    assert dealer_stats() == {"draws": 0, "traces": 0}
    # the snapshot is a copy
    dealer_stats()["draws"] = 99
    assert additive2pc.dealer_stats()["draws"] == 0


# -- a RING32 2PC phase at smoke geometry ------------------------------------

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, WAVE, CLASSES = 8, 8, 2, 2
POOL = 32                        # 4 batches -> 2 waves of 2


def test_warm_wave_draws_from_cached_programs():
    key = jax.random.key(0)
    pp = proxy_mod.random_proxy(key, CFG, SPEC, seq_len=SEQ,
                                n_classes=CLASSES)
    pool = np.random.default_rng(0).integers(0, CFG.vocab_size, (POOL, SEQ))
    cfg = ExecConfig(wave=WAVE, batch=BATCH, ring=RING32, protocol="2pc")
    run = PhaseRun(cfg, jax.random.fold_in(key, 1), pp, CFG, pool, SPEC)
    assert run.n_waves == 2
    s0 = dealer_stats()
    run.dispatch(0)
    s1 = dealer_stats()
    run.dispatch(1)
    s2 = dealer_stats()
    run.drain()
    _, rep = run.finish()
    wave0 = s1["draws"] - s0["draws"]
    assert wave0 > 0
    assert s2["draws"] - s1["draws"] == wave0
    assert s2["traces"] == s1["traces"]          # the second wave: none
    assert rep.device.dealer_draws == 2 * wave0
    assert rep.device.dealer_traces == s1["traces"] - s0["traces"]
    assert rep.agrees()
    d = rep.device.as_dict()
    assert (d["dealer_draws"], d["dealer_traces"]) == (
        rep.device.dealer_draws, rep.device.dealer_traces)
