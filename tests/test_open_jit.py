"""The 2PC openings, compiled once per signature.

`Additive2PC._open_flight` (the Beaver openings of `mul` and `matmul`)
and the online half of the RING32 dealer truncation run a module-level
jitted core (masking, the sum of both components, and for a truncation
the public shift and the subtraction) with the ledger record outside
the jit. Contracts:
  1. BITWISE — `mul`, `matmul` (2-D, batched left operand, the kernel
     combine route on its jnp fallback) and `trunc` (default and folded
     shift), vmapped as the wave forward runs them, equal the same call
     under `jax.disable_jit()`.
  2. SAME RECORDS — every opening records what the eager one records,
     field for field, with and without a flight batcher, also under an
     abstract trace.
  3. CACHED — a shape's second opening traces nothing; a warm RING32
     2PC phase opens from cached programs only.
  4. TAPE — under a WireTape the flight's messages are the eager bytes;
     without one no payload is built.
"""
import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_targets import TINY_TARGET
from repro.core import proxy as proxy_mod
from repro.core.executor import ExecConfig, PhaseRun
from repro.core.proxy import ProxySpec
from repro.mpc import comm, fusion
from repro.mpc.protocols import additive2pc
from repro.mpc.protocols.additive2pc import (
    Additive2PC, matmul_triple, mul_triple, open_stats, reset_open_stats,
    trunc_pair)
from repro.mpc.ring import RING32
from repro.mpc.sharing import share

BE = Additive2PC()
OPEN_OPS = ("beaver_mul", "beaver_matmul", "trunc_open")


def _sh(seed, shape, lo=-2.0, hi=2.0):
    n = int(np.prod(shape))
    return share(jax.random.key(seed),
                 jnp.linspace(lo, hi, n).reshape(shape), RING32)


def _ops():
    """(name, op(key) -> output share components) on fixed operands."""
    x35, y35 = _sh(1, (3, 5)), _sh(2, (3, 5), -1.0, 1.0)
    xcol, yrow = _sh(3, (4, 1)), _sh(4, (1, 6), -1.0, 1.0)
    x46, w63 = _sh(5, (4, 6)), _sh(6, (6, 3), -0.5, 0.5)
    xb = _sh(7, (2, 4, 6))
    w68 = _sh(8, (6, 8), -0.5, 0.5)
    xt = _sh(9, (2, 7), -3.0, 3.0)
    f = RING32.frac_bits
    return {
        "mul": lambda k: BE.mul(x35, y35, k).sh,
        "mul_broadcast": lambda k: BE.mul(xcol, yrow, k).sh,
        "matmul": lambda k: BE.matmul(x46, w63, k).sh,
        "matmul_batched": lambda k: BE.matmul(xb, w63, k).sh,
        "matmul_kernel_route": lambda k: BE.matmul(
            xb, w68, k, combine_impl="auto").sh,
        "trunc_default": lambda k: BE.trunc(
            BE.mul(xt, xt, k), jax.random.fold_in(k, 1)).sh,
        "trunc_folded": lambda k: BE.trunc(
            BE.mul(xt, xt, k), jax.random.fold_in(k, 1), shift=f + 5).sh,
    }


OPS = list(_ops())
LANES = ["plain", 1, 2]


def _run(op, lanes, seed=0):
    if lanes == "plain":
        return op(jax.random.key(seed))
    return jax.vmap(op)(jax.random.split(jax.random.key(seed), lanes))


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("op_name", OPS)
def test_opening_bitwise_equals_eager(op_name, lanes):
    op = _ops()[op_name]
    compiled = np.asarray(_run(op, lanes))
    with jax.disable_jit():
        eager = np.asarray(_run(op, lanes))
    assert compiled.dtype == eager.dtype == RING32.dtype
    np.testing.assert_array_equal(compiled, eager)
    if lanes == 2:          # the two lanes draw from different keys
        assert not np.array_equal(compiled[0], compiled[1])


def test_trunc_decodes_the_shifted_value():
    """The compiled truncation is exact to +-1 LSB at the folded shift:
    the raw product carries 2f fractional bits, f - 5 after it."""
    f = RING32.frac_bits
    x = _sh(9, (2, 7), -3.0, 3.0)
    t = BE.trunc(BE.mul(x, x, jax.random.key(0)), jax.random.key(1),
                 shift=f + 5)
    assert t.fb == x.fb - (f + 5)
    got = RING32.decode_at(np.asarray(t.sh).sum(0), f - 5)
    want = np.linspace(-3.0, 3.0, 14).reshape(2, 7) ** 2
    np.testing.assert_allclose(np.asarray(got), want, atol=2.0 ** -(f - 6))


def _beaver_mul(x, y, key):
    shape = jnp.broadcast_shapes(x.shape, y.shape)
    a, b, c = mul_triple(key, shape, RING32)
    eps = (jnp.broadcast_to(x.sh, a.sh.shape) - a.sh).sum(0)
    dlt = (jnp.broadcast_to(y.sh, b.sh.shape) - b.sh).sum(0)
    z = c.sh + eps * b.sh + dlt * a.sh
    return z.at[0].add(eps * dlt)


def _beaver_matmul(x, y, key):
    a, b, c = matmul_triple(key, x.shape, y.shape, RING32)
    eps, dlt = (x.sh - a.sh).sum(0), (y.sh - b.sh).sum(0)
    mm = functools.partial(jnp.matmul, preferred_element_type=jnp.int32)
    z = c.sh + mm(jnp.stack([eps, eps]), b.sh) \
        + mm(a.sh, jnp.stack([dlt, dlt]))
    return z.at[0].add(mm(eps, dlt))


def _dealer_trunc(x, key, shift):
    r, r_t = trunc_pair(key, x.shape, RING32, shift)
    m_t = (x.sh + r.sh).sum(0) >> shift
    return jnp.stack([m_t, jnp.zeros_like(m_t)]) - r_t.sh


# (name, backend call, the protocol written out from the dealer's draws)
PROTOCOL = {
    "mul_broadcast": (lambda k: BE.mul(_sh(3, (4, 1)), _sh(4, (1, 6)), k),
                      lambda k: _beaver_mul(_sh(3, (4, 1)), _sh(4, (1, 6)),
                                            k)),
    "matmul_batched": (lambda k: BE.matmul(_sh(7, (2, 4, 6)),
                                           _sh(6, (6, 3)), k),
                       lambda k: _beaver_matmul(_sh(7, (2, 4, 6)),
                                                _sh(6, (6, 3)), k)),
    "trunc_folded": (lambda k: BE.trunc(_sh(9, (2, 7)), k,
                                        shift=RING32.frac_bits + 5),
                     lambda k: _dealer_trunc(_sh(9, (2, 7)), k,
                                             RING32.frac_bits + 5)),
}


@pytest.mark.parametrize("op_name", list(PROTOCOL))
def test_opening_equals_the_written_protocol(op_name):
    """Bitwise the parent's eager formulas: the masks, the sum of both
    components, the public shift, from the triple or pair the key
    draws."""
    call, written = PROTOCOL[op_name]
    key = jax.random.key(11)
    np.testing.assert_array_equal(np.asarray(call(key).sh),
                                  np.asarray(written(key)))


def _records(op, *, fused, disable_jit):
    ctx = jax.disable_jit() if disable_jit else contextlib.nullcontext()
    with ctx, comm.ledger_scope() as led:
        with fusion.flight_scope(fused):
            _run(op, 2)
            _run(op, 2, seed=1)
    return [dataclasses.astuple(r) for r in led.records]


@pytest.mark.parametrize("fused", [False, True], ids=["eager", "fused"])
@pytest.mark.parametrize("op_name", ["mul_broadcast", "matmul_batched",
                                     "trunc_folded"])
def test_records_equal_eager_records(op_name, fused):
    op = _ops()[op_name]
    recs = _records(op, fused=fused, disable_jit=False)
    assert recs == _records(op, fused=fused, disable_jit=True)
    ops = [r[0] for r in recs]
    if fused:
        assert any(o.startswith("fused.") for o in ops)
    else:
        assert sum(o in OPEN_OPS for o in ops) == \
            (4 if op_name.startswith("trunc") else 2)


def test_records_fire_under_eval_shape():
    """The TraceEngine probe runs the forward abstractly: the cores
    trace, the backend still records every opening."""
    x, y = _sh(1, (3, 5)), _sh(2, (3, 5))
    with comm.ledger_scope() as led:
        out = jax.eval_shape(
            lambda k: BE.trunc(BE.mul(x, y, k),
                               jax.random.fold_in(k, 1)).sh,
            jax.random.key(0))
    assert out.shape == (2, 3, 5) and out.dtype == jnp.int32
    assert [r.op for r in led.records] == [
        "offline.mul_triple", "beaver_mul", "offline.trunc_pair",
        "trunc_open"]


@pytest.mark.parametrize("op_name", ["mul", "matmul_batched",
                                     "trunc_default"])
def test_second_opening_of_a_shape_traces_nothing(op_name):
    op = _ops()[op_name]
    _run(op, 2)
    s0 = open_stats()
    _run(op, 2, seed=1)
    s1 = open_stats()
    assert s1["traces"] == s0["traces"]
    assert s1["calls"] == s0["calls"] + (2 if op_name.startswith("trunc")
                                         else 1)


def test_reset_open_stats():
    x = _sh(1, (2,))
    BE.mul(x, x, jax.random.key(0))
    assert open_stats()["calls"] >= 1
    reset_open_stats()
    assert open_stats() == {"calls": 0, "traces": 0}
    # the snapshot is a copy
    open_stats()["calls"] = 99
    assert additive2pc.open_stats()["calls"] == 0


# -- the wire payload --------------------------------------------------------

def _tape_msgs(op, key, disable_jit=False):
    ctx = jax.disable_jit() if disable_jit else contextlib.nullcontext()
    tape = comm.WireTape(2)
    with ctx, comm.ledger_scope(), comm.wire_tape_scope(tape):
        op(key)
    return [(f.op, f.nbytes, f.msgs) for f in tape.flights]


def _parts(t):
    return [np.asarray(t[p]).tobytes() for p in (0, 1)]


@pytest.mark.parametrize("op_name", ["mul_broadcast", "matmul_batched",
                                     "trunc_folded"])
def test_tape_carries_the_eager_payload(op_name):
    op = _ops()[op_name]
    key = jax.random.key(5)
    got = _tape_msgs(op, key)
    assert got == _tape_msgs(op, key, disable_jit=True)
    for name, nbytes, msgs in got:
        assert name in OPEN_OPS
        assert [(m.src, m.dst) for m in msgs][:2] == [(0, 1), (1, 0)]
        assert sum(len(m.data) for m in msgs) == nbytes


def test_tape_payload_is_the_masked_components():
    """Party p sends its components of x - a and y - b, from the
    triple the same key draws."""
    x, y = _sh(3, (4, 1)), _sh(4, (1, 6))
    key = jax.random.key(2)
    ((op, _, msgs),) = _tape_msgs(lambda k: BE.mul(x, y, k), key)
    a, b, _ = mul_triple(key, (4, 6), RING32)
    eps = jnp.broadcast_to(x.sh, (2, 4, 6)) - a.sh
    dlt = jnp.broadcast_to(y.sh, (2, 4, 6)) - b.sh
    assert op == "beaver_mul"
    want = [e for t in (eps, dlt) for e in _parts(t)]
    assert [m.data for m in msgs] == want
    assert [(m.src, m.dst) for m in msgs] == [(0, 1), (1, 0)] * 2


def test_tape_trunc_payload_is_x_plus_r():
    x = _sh(3, (4, 3))
    key = jax.random.key(2)
    ((op, _, msgs),) = _tape_msgs(lambda k: BE.trunc(x, k), key)
    r, _ = trunc_pair(key, (4, 3), RING32)
    assert op == "trunc_open"
    assert [m.data for m in msgs] == _parts(x.sh + r.sh)


@pytest.mark.parametrize("op_name", ["mul", "matmul", "trunc_default"])
def test_no_payload_without_a_tape(op_name, monkeypatch):
    seen = []
    real = comm.record

    def spy(op, *a, payload=None, **kw):
        seen.append((op, payload))
        return real(op, *a, payload=payload, **kw)

    monkeypatch.setattr(comm, "record", spy)
    with comm.ledger_scope():
        _run(_ops()[op_name], 2)
    opens = [p for op, p in seen if op in OPEN_OPS]
    assert opens and all(p is None for p in opens)


# -- a RING32 2PC phase at smoke geometry ------------------------------------

CFG = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                          d_model=32, n_heads=2, n_kv_heads=2, d_head=16,
                          d_ff=64)
SPEC = ProxySpec(1, 2, 4)
SEQ, BATCH, WAVE, CLASSES = 8, 8, 2, 2
POOL = 32                        # 4 batches -> 2 waves of 2


def _phase(fuse):
    key = jax.random.key(0)
    pp = proxy_mod.random_proxy(key, CFG, SPEC, seq_len=SEQ,
                                n_classes=CLASSES)
    pool = np.random.default_rng(0).integers(0, CFG.vocab_size, (POOL, SEQ))
    cfg = ExecConfig(wave=WAVE, batch=BATCH, ring=RING32, protocol="2pc",
                     fuse=fuse)
    run = PhaseRun(cfg, jax.random.fold_in(key, 1), pp, CFG, pool, SPEC)
    for wi in range(run.n_waves):
        run.dispatch(wi)
    run.drain()
    return run.finish()


def test_warm_phase_opens_from_cached_programs():
    _phase(fuse=True)                            # cold: traces the cores
    out_f, rep_f = _phase(fuse=True)
    out_e, rep_e = _phase(fuse=False)
    for rep in (rep_f, rep_e):
        assert rep.device.open_traces == 0
        assert rep.agrees()
    # one call per opening: the unfused ledger lists each opening once
    # a wave; fusion regroups the records, not the openings
    n_open = sum(r.op in OPEN_OPS for r in rep_e.ledger.records)
    assert n_open > 0
    assert rep_e.device.open_calls == n_open
    assert rep_f.device.open_calls == n_open
    np.testing.assert_array_equal(np.asarray(out_f.sh),
                                  np.asarray(out_e.sh))
    d = rep_f.device.as_dict()
    assert (d["open_calls"], d["open_traces"]) == (n_open, 0)
