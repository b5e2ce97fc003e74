"""Additive 2PC with a trusted dealer — the CrypTen trust model.

Share layout: two uniform additive components on the leading axis,
`sh[0] + sh[1] = value` (mod 2**bits). Multiplication consumes Beaver
triples and (on the TPU ring) truncation pairs from an offline dealer
(crypto provider): the dealer is a PRNG-keyed pure function so triples
are reproducible and jit-friendly; in deployment the dealer seed lives
on the crypto-provider host and shares are streamed ahead of the online
phase.

Every byte the dealer ships is recorded into the ambient ledger's
OFFLINE channel (`tag="offline"`, 0 rounds): it never rides the online
wire, is excluded from `Ledger.nbytes`/makespan, and is reported
separately (`Ledger.offline_nbytes`) — the cost axis on which the
dealer-free replicated3pc backend wins.

Online wire model: an opening flight carries both parties' components
of every tensor at once — 1 round, `2 * elem_bytes * elems` bytes.
These flights are fusible under the deferred-reconstruction convention
(mpc/fusion.py): messages are mask components (`x - a`, `z + r`)
computable before the flight departs, with dependence on previously
opened values entering only through PUBLIC reconstructions both parties
apply after the fact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.mpc.ring import RingSpec
from repro.mpc import comm
from repro.mpc.protocols.base import BackendDefaults, numel
from repro.spans import span


def _share_raw(key: jax.Array, enc: jax.Array, ring: RingSpec) -> jax.Array:
    r = ring.rand(key, enc.shape)
    return jnp.stack([r, enc - r])


def _record_offline(op: str, ring: RingSpec, n_elems: int) -> None:
    """Dealer-shipped correlated randomness: n_elems ring elements,
    additively shared to both parties."""
    comm.record(op, rounds=0, nbytes=2 * ring.elem_bytes * n_elems,
                numel=n_elems, tag="offline")


# ---------------------------------------------------------------------------
# the dealer (re-exported by mpc/beaver.py for back-compat)
#
# Each draw is a module-level jitted core, static in shapes, ring and
# shift, under a wrapper that records the offline bytes OUTSIDE the jit:
# one record per draw, not per trace, also under an outer trace (the
# probe's eval_shape, the shardmap wave's jit). The material depends
# only on the key and the shape, so the compiled draw is bitwise the
# eager one, and a warm wave dispatches one cached program per draw
# instead of ~40 eager ops.
# ---------------------------------------------------------------------------

# `draws` counts wrapper calls, `traces` runs of a core's Python body,
# which happen only when jit traces it: the executor snapshots both
# around a phase to witness that a warm phase draws from cached programs
_dealer_stats = {"draws": 0, "traces": 0}


def dealer_stats() -> dict:
    """Snapshot of the dealer's draw and trace counters."""
    return dict(_dealer_stats)


def reset_dealer_stats() -> None:
    for k in _dealer_stats:
        _dealer_stats[k] = 0


@functools.partial(jax.jit, static_argnums=(1, 2))
def _mul_triple_raw(key, shape, ring):
    _dealer_stats["traces"] += 1
    ka, kb, k1, k2, k3 = jax.random.split(key, 5)
    a = ring.rand(ka, shape)
    b = ring.rand(kb, shape)
    c = a * b   # ring product, wraps mod 2**bits
    return (_share_raw(k1, a, ring), _share_raw(k2, b, ring),
            _share_raw(k3, c, ring))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _matmul_triple_raw(key, a_shape, b_shape, ring):
    _dealer_stats["traces"] += 1
    ka, kb, k1, k2, k3 = jax.random.split(key, 5)
    a = ring.rand(ka, a_shape)
    b = ring.rand(kb, b_shape)
    c = jnp.matmul(a, b, preferred_element_type=ring.dtype)
    return (_share_raw(k1, a, ring), _share_raw(k2, b, ring),
            _share_raw(k3, c, ring))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _trunc_pair_raw(key, shape, ring, shift):
    _dealer_stats["traces"] += 1
    kr, k1, k2 = jax.random.split(key, 3)
    # r drawn from the "safe" range [0, 2**(bits-2)) to avoid sign wrap
    r = (ring.rand(kr, shape).astype(jnp.uint32 if ring.bits == 32
                                     else jnp.uint64)
         >> 2).astype(ring.dtype)
    r_t = r >> shift             # arithmetic shift of non-negative r
    return _share_raw(k1, r, ring), _share_raw(k2, r_t, ring)


def _shares(op: str, ring: RingSpec, raw) -> tuple:
    """A draw's host half: its offline record, its count, its Shares.
    The record counts every element the dealer ships (both parties)."""
    from repro.mpc.sharing import Share
    _record_offline(op, ring, sum(numel(r.shape[1:]) for r in raw))
    _dealer_stats["draws"] += 1
    return tuple(Share(r, ring) for r in raw)


def mul_triple(key: jax.Array, shape, ring: RingSpec):
    """Elementwise triple: a*b = c (c at 2*frac scale — consumed pre-trunc)."""
    with span("mpc.dealer"):
        return _shares("offline.mul_triple", ring,
                       _mul_triple_raw(key, tuple(shape), ring))


def matmul_triple(key: jax.Array, a_shape, b_shape, ring: RingSpec):
    """Matrix triple A@B = C for arbitrary batched matmul shapes."""
    with span("mpc.dealer"):
        return _shares("offline.matmul_triple", ring,
                       _matmul_triple_raw(key, tuple(a_shape),
                                          tuple(b_shape), ring))


def trunc_pair(key: jax.Array, shape, ring: RingSpec,
               shift: int | None = None):
    """Dealer-assisted truncation pair (r, r >> shift) — SecureML-style.

    Exact (±1 LSB) truncation for the int32 TPU ring where local
    truncation's wrap probability is too high. `shift` defaults to one
    canonical scale (frac_bits); scale-carrying shares hand in their
    whole accumulated excess (e.g. f+5 after a folded mean) so ONE pair
    clears what eager mode paid as several.
    """
    shift = ring.frac_bits if shift is None else shift
    with span("mpc.dealer"):
        return _shares("offline.trunc_pair", ring,
                       _trunc_pair_raw(key, tuple(shape), ring, shift))


def triple_bytes(a_shape, b_shape, c_shape, ring: RingSpec) -> int:
    """Offline bytes the dealer ships for one triple (both parties)."""
    n = 0
    for s in (a_shape, b_shape, c_shape):
        n += numel(s)
    return 2 * ring.elem_bytes * n


# ---------------------------------------------------------------------------
# the openings
#
# As for the dealer: each opening's online arithmetic (masking, the sum
# of both components, and for a truncation the public shift and the
# subtraction) is a module-level jitted core, static in ring (and
# shift), keyed by jit on shapes. The backend records the flight
# OUTSIDE it, so records fire once per opening, also under an outer
# trace. Ring arithmetic wraps either way: the compiled opening is
# bitwise the eager one.
# ---------------------------------------------------------------------------

# `calls` counts openings (Beaver flights and truncation opens),
# `traces` runs of a core's Python body (only when jit traces it): the
# executor snapshots both around a phase, as for the dealer
_open_stats = {"calls": 0, "traces": 0}


def open_stats() -> dict:
    """Snapshot of the openings' call and trace counters."""
    return dict(_open_stats)


def reset_open_stats() -> None:
    for k in _open_stats:
        _open_stats[k] = 0


def _masked(xs, masks) -> tuple:
    """Each party's masked components `x - mask`, x broadcast to the
    mask's (dealer-drawn) shape."""
    return tuple(jnp.broadcast_to(x, m.shape) - m for x, m in zip(xs, masks))


@functools.partial(jax.jit, static_argnums=(2,))
def _open_raw(xs, masks, ring):
    _open_stats["traces"] += 1
    return tuple(t[0] + t[1] for t in _masked(xs, masks))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _trunc_open_raw(x, r, r_t, ring, shift):
    _open_stats["traces"] += 1
    masked = x + r
    m_t = (masked[0] + masked[1]) >> shift
    pub = jnp.stack([m_t, jnp.zeros_like(m_t)])
    return pub - r_t


# ---------------------------------------------------------------------------
# the backend
# ---------------------------------------------------------------------------

class Additive2PC(BackendDefaults):
    name = "2pc"
    n_parties = 2
    n_wire_parties = 2

    # -- sharing --------------------------------------------------------
    def share_encoded(self, key: jax.Array, enc: jax.Array,
                      ring: RingSpec) -> jax.Array:
        return _share_raw(key, enc, ring)

    def from_public(self, enc: jax.Array) -> jax.Array:
        return jnp.stack([enc, jnp.zeros_like(enc)])

    def open_bytes(self, ring: RingSpec, n: int) -> int:
        return 2 * ring.elem_bytes * n

    # -- openings -------------------------------------------------------
    def _open_flight(self, op: str, xs, masks, ring: RingSpec, *, n: int,
                     flops: int = 0):
        """Open `x - mask` for every (x, mask) pair in ONE simultaneous
        message flight.

        All tensors of a flight ride the same round trip (each party
        sends its masked components of every tensor at once), so the
        flight costs 1 round and 2 * elem_bytes * total-elements on the
        wire. This is the unit the wave executor schedules: under
        comm.wave_scope the flight's bytes scale with the wave while
        latency-bound flights keep their rounds.

        The masking and the sum run as one cached program (`_open_raw`;
        each x broadcasts to its mask's shape inside it); the record
        stays outside it. The record's payload IS the flight: party p's
        masked components of every tensor, routed to the peer — what
        `--wire` runs serialize onto the real transport
        (comm.WireTape). It is built, eagerly from the same operands,
        only when a WireTape is ambient: nothing else reads it.
        """
        with span("mpc.open"):
            payload = None
            if comm.get_wire_tape() is not None:
                payload = [(p, 1 - p, t[p])
                           for t in _masked(xs, masks) for p in (0, 1)]
            wire_elems = sum(numel(m.shape[1:]) for m in masks)
            comm.record(op, rounds=1,
                        nbytes=2 * ring.elem_bytes * wire_elems,
                        numel=n, flops=flops, tag="bw", payload=payload)
            _open_stats["calls"] += 1
            return _open_raw(xs, masks, ring)

    # -- truncation -----------------------------------------------------
    def trunc(self, x, key: jax.Array | None, *, shift: int | None = None,
              site: str | None = None):
        """Divide shares by 2**shift (default: one canonical scale).

        RING64 (or keyless boundary trunc): local arithmetic shift of
        both components — correct up to ±1 LSB w.p. 1 - |v|/2**(bits-1)
        per element (CrypTen's choice). RING32: dealer-assisted pair
        (exact): open (x+r), shift publicly, subtract the dealer's share
        of r>>shift. Costs one opening round plus the pair's offline
        bytes — the SAME cost for any shift, which is why folding a
        chain of deferred rescales into one trunc(shift=excess) is a
        straight win for the dealer channel.

        The online half (mask, open, shift, subtract) runs as one cached
        program (`_trunc_open_raw`, static in ring and shift), with the
        `trunc_open` record outside it; the record's payload, each
        party's masked component, is built only when a WireTape is
        ambient."""
        ring = x.ring
        shift = ring.frac_bits if shift is None else shift
        out_fb = x.fb - shift
        if ring.bits >= 64 or key is None:
            s0 = x.sh[0] >> shift
            s1 = -((-x.sh[1]) >> shift)
            return x.with_scale(jnp.stack([s0, s1]), out_fb)
        # dealer-assisted exact truncation (TPU ring)
        with span("mpc.trunc", site=site):
            r, r_t = trunc_pair(key, x.shape, ring, shift)
            with span("mpc.open"):
                payload = None
                if comm.get_wire_tape() is not None:
                    masked = x.sh + r.sh
                    payload = [(0, 1, masked[0]), (1, 0, masked[1])]
                comm.record("trunc_open", rounds=1,
                            nbytes=2 * ring.elem_bytes * numel(x.shape),
                            numel=numel(x.shape), tag="bw",
                            payload=payload)
                _open_stats["calls"] += 1
                sh = _trunc_open_raw(x.sh, r.sh, r_t.sh, ring, shift)
            return x.with_scale(sh, out_fb)

    # -- multiplication -------------------------------------------------
    def mul(self, x, y, key: jax.Array):
        """Beaver multiply. One opening round for (eps, delta); returns
        the raw product — `mpc/ops.py` owns the scale bookkeeping."""
        ring = x.ring
        shape = jnp.broadcast_shapes(x.shape, y.shape)
        a, b, c = mul_triple(key, shape, ring)
        n = numel(shape)
        eps_o, dlt_o = self._open_flight("beaver_mul", (x.sh, y.sh),
                                         (a.sh, b.sh), ring,
                                         n=n, flops=4 * n)
        with span("mpc.combine"):
            z = c.sh + eps_o * b.sh + dlt_o * a.sh
            z = z.at[0].add(eps_o * dlt_o)
            return x.with_sh(z)

    def matmul(self, x, y, key: jax.Array, *,
               combine_impl: str | None = None):
        """Beaver matrix-triple matmul. One opening round.

        Bytes on the wire: |eps| + |delta| per party = (numel(x)+numel(y))
        elems — crucially *not* numel(x)*cols bytes: the triple reuse is
        what makes 2PC matmul bandwidth-, not latency-, dominated.

        `combine_impl` routes the post-open combine of 2-D RING32
        matmuls through the fused Pallas kernel
        (`kernels/ops.secure_matmul`): both parties'
        `z_p = c_p + eps@b_p + a_p@dlt (+ p0: eps@dlt)` in one tiled
        launch. Exact wrapping int32 arithmetic — bitwise-identical to
        the inline combine ("auto" compiles on TPU, falls back to the
        jnp reference elsewhere).
        """
        ring = x.ring
        a, b, c = matmul_triple(key, x.shape, y.shape, ring)
        n = numel(x.shape) + numel(y.shape)
        m, k = x.shape[-2], x.shape[-1]
        n_out = y.shape[-1]
        batch = numel(x.shape[:-2])
        eps_o, dlt_o = self._open_flight("beaver_matmul", (x.sh, y.sh),
                                         (a.sh, b.sh), ring,
                                         n=n, flops=2 * batch * m * k * n_out)
        # party-local: z_p = c_p + eps@b_p + a_p@dlt ; party0 adds eps@dlt
        # Kernel eligibility: 2-D weights on the right. Batched left
        # operands ((..., M, K) @ (K, N) — the forward's big projection
        # matmuls) flatten their batch dims into rows: row-wise int32
        # ring arithmetic is exact, so the flattened combine is bitwise
        # identical to the per-batch inline one.
        with span("mpc.combine"):
            if combine_impl is not None and ring.bits == 32 \
                    and y.sh.ndim == 3 and x.sh.ndim >= 3:
                from repro.kernels import ops as kops
                eps2 = eps_o.reshape(-1, k)
                z = kops.secure_matmul(eps2, dlt_o,
                                       a.sh.reshape(2, -1, k), b.sh,
                                       c.sh.reshape(2, -1, n_out),
                                       impl=combine_impl)
                return x.with_sh(z.reshape(c.sh.shape))
            eb = jnp.matmul(jnp.stack([eps_o, eps_o]), b.sh,
                            preferred_element_type=ring.dtype)
            ad = jnp.matmul(a.sh, jnp.stack([dlt_o, dlt_o]),
                            preferred_element_type=ring.dtype)
            z = c.sh + eb + ad
            ed = jnp.matmul(eps_o, dlt_o, preferred_element_type=ring.dtype)
            z = z.at[0].add(ed)
            return x.with_sh(z)
