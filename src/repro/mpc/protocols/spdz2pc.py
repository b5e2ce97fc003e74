"""SPDZ-style MAC'd additive 2PC — the malicious-security tier.

Share layout: FOUR rows on the leading axis — two value components and
two MAC components under the dealer's global key alpha:

    sh[0] + sh[1] = value          (mod 2**bits)
    sh[2] + sh[3] = alpha * value  (mod 2**bits)

Every linear op in `mpc/ops` is automatically MAC-transparent (the MAC
relation is linear in the value), the two affine exceptions
(`reconstruct`, `add_public_encoded`) dispatch here, and the scheme's
own ops below maintain the invariant through Beaver multiplication and
dealer-assisted truncation.

Trust model (SPDZ with a trusted dealer for preprocessing): the dealer
ships MAC'd correlated randomness on the PR 4 offline channel — each
tensor now costs 4 components (value + MAC, both parties), so offline
bytes double versus semi-honest 2PC. Online, the parties can deviate
arbitrarily: correctness is enforced by information-theoretic MACs.

Openings are PARTIAL — parties exchange only value components (the same
`2 * elem_bytes` wire profile as semi-honest 2PC; MAC components never
ride the wire). Each partial open enqueues a deferred check obligation

    sigma = (sh[2] + sh[3]) - alpha * opened_value

into the ambient `mac_scope` state; all obligations are verified by ONE
batched random-linear-combination check at the forward's public
boundary (`mac_check_flight`, invoked by `MPCEngine.entropy_head`) —
constant-size regardless of how many values were opened, recorded as a
1-round tag="bw" flight so the batcher fuses it like any Beaver open,
plus the dealer's one-time MAC-key shipment on the offline channel.

Triples are authenticated by SACRIFICE: each multiply consumes a second
dealer triple and burns it in a 1-round correlation check (t*a - a'
style), so a cheating dealer-channel or a tampered triple is caught
before its product is used. The sacrifice opening is a mask-component
flight — fusible under the deferred-reconstruction convention exactly
like the Beaver open it precedes.

Truncation: local shifting is NOT MAC-preserving (and a malicious party
could shift dishonestly), so BOTH rings pay the dealer trunc pair + one
opening round — the semi-honest RING64 free local shift is one of the
costs malicious security visibly buys back (`bench_fusion`'s overhead
curve).

Tamper injection (tests only): `tamper_scope(fn)` installs a fire-once
hook applied to the next stacked share tensor entering a partial open —
the adversary's one bit flip. The subsequent MAC check aborts with
`MacCheckError`; the semi-honest backends accept the same tamper
silently (pinned by tests/test_conformance.py).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator

import jax
import jax.numpy as jnp

from repro.mpc.ring import RingSpec, RING32, RING64
from repro.mpc import comm
from repro.mpc.protocols.base import BackendDefaults, numel
from repro.spans import span

_MAC_SEED = 0xA1C


class MacCheckError(AssertionError):
    """A batched SPDZ MAC check failed: an opened value was tampered."""


_state = threading.local()


def _ring_of(sh: jax.Array) -> RingSpec:
    """Recover the RingSpec from a stacked share's dtype (the affine
    hooks receive raw arrays; only two rings exist)."""
    return RING64 if jnp.dtype(sh.dtype).itemsize == 8 else RING32


def mac_key(ring: RingSpec):
    """The dealer's global MAC key alpha and its additive split
    (alpha0 + alpha1 = alpha). Deterministic per ring — the simulation
    stands in for the dealer's one-time key generation; its shipment to
    the parties is priced by `mac_check_flight` (offline.mac_key)."""
    k = jax.random.key(_MAC_SEED + ring.bits)
    alpha = ring.rand(k, ())
    a0 = ring.rand(jax.random.fold_in(k, 1), ())
    return alpha, a0, alpha - a0


# ---------------------------------------------------------------------------
# deferred MAC-check state + the test-only tamper hook
# ---------------------------------------------------------------------------

class MacState:
    """Deferred MAC-check obligations of one verification scope.

    Each partial open appends sigma = gamma_sum - alpha * opened; honest
    executions keep every sigma identically zero. Obligations produced
    under a trace (vmap/eval_shape tracers) cannot be checked eagerly
    and are counted in `n_traced` instead — the executed tamper tests
    run the forward eagerly, where every sigma is concrete."""

    def __init__(self) -> None:
        self.sigmas: list[tuple[str, jax.Array]] = []
        self.n_opened = 0
        self.n_traced = 0

    def verify(self) -> None:
        """The batched check: abort on any nonzero sigma."""
        import numpy as np
        for op, sg in self.sigmas:
            if bool(np.any(np.asarray(sg) != 0)):
                raise MacCheckError(
                    f"spdz2pc MAC check failed on {op!r}: an opened value "
                    f"or its MAC was tampered with — aborting")
        self.sigmas.clear()


@contextlib.contextmanager
def mac_scope() -> Iterator[MacState]:
    """Collect MAC obligations for every partial open inside; verify via
    `MacState.verify()` (the engine boundary calls it automatically
    through `mac_check_flight`)."""
    prev = getattr(_state, "mac", None)
    st = MacState()
    _state.mac = st
    try:
        yield st
    finally:
        _state.mac = prev


def get_mac_state() -> MacState | None:
    return getattr(_state, "mac", None)


@contextlib.contextmanager
def tamper_scope(fn) -> Iterator[None]:
    """TEST-ONLY adversary: `fn(stacked) -> stacked` is applied ONCE to
    the next share tensor entering a partial open (rows 0/1 = value
    components, rows 2/3 = MAC components — flip a bit in either)."""
    prev = getattr(_state, "tamper", None)
    _state.tamper = {"fn": fn, "fired": False}
    try:
        yield
    finally:
        _state.tamper = prev


def _maybe_tamper(sh: jax.Array) -> jax.Array:
    t = getattr(_state, "tamper", None)
    if t is None or t["fired"]:
        return sh
    t["fired"] = True
    return t["fn"](sh)


def _note_open(op: str, opened: jax.Array, gamma: jax.Array,
               ring: RingSpec) -> None:
    st = get_mac_state()
    if st is None:
        return
    st.n_opened += 1
    if isinstance(opened, jax.core.Tracer) or isinstance(gamma,
                                                         jax.core.Tracer):
        st.n_traced += 1
        return
    alpha, _, _ = mac_key(ring)
    st.sigmas.append((op, gamma - alpha * opened))


# ---------------------------------------------------------------------------
# the MAC'd dealer
# ---------------------------------------------------------------------------

def _share_mac(key: jax.Array, enc: jax.Array, ring: RingSpec) -> jax.Array:
    """(4, *shape): additive split of enc stacked on an additive split
    of alpha * enc."""
    alpha, _, _ = mac_key(ring)
    kx, km = jax.random.split(key)
    rx = ring.rand(kx, enc.shape)
    rm = ring.rand(km, enc.shape)
    gm = alpha * enc
    return jnp.stack([rx, enc - rx, rm, gm - rm])


def _record_offline_mac(op: str, ring: RingSpec, n_elems: int) -> None:
    """Dealer-shipped MAC'd correlated randomness: each of n_elems ring
    elements costs 4 components (value + MAC, both parties) — double
    the semi-honest dealer's bytes, the offline price of authentication."""
    comm.record(op, rounds=0, nbytes=4 * ring.elem_bytes * n_elems,
                numel=n_elems, tag="offline")


def _mac_mul_triple(key: jax.Array, shape, ring: RingSpec):
    with span("mpc.dealer"):
        ka, kb, k1, k2, k3 = jax.random.split(key, 5)
        a = ring.rand(ka, shape)
        b = ring.rand(kb, shape)
        c = a * b
        _record_offline_mac("offline.mul_triple", ring, 3 * numel(shape))
        return (_share_mac(k1, a, ring), _share_mac(k2, b, ring),
                _share_mac(k3, c, ring))


def _mac_matmul_triple(key: jax.Array, a_shape, b_shape, ring: RingSpec):
    with span("mpc.dealer"):
        ka, kb, k1, k2, k3 = jax.random.split(key, 5)
        a = ring.rand(ka, a_shape)
        b = ring.rand(kb, b_shape)
        c = jnp.matmul(a, b, preferred_element_type=ring.dtype)
        _record_offline_mac("offline.matmul_triple", ring,
                            numel(a_shape) + numel(b_shape) + numel(c.shape))
        return (_share_mac(k1, a, ring), _share_mac(k2, b, ring),
                _share_mac(k3, c, ring))


def _mac_trunc_pair(key: jax.Array, shape, ring: RingSpec, shift: int):
    """The dealer's MAC'd (r, r >> shift), r from the "safe" range
    [0, 2**(bits-2)) to avoid sign wrap."""
    with span("mpc.dealer"):
        kr, k1, k2 = jax.random.split(key, 3)
        utype = jnp.uint32 if ring.bits == 32 else jnp.uint64
        r = (ring.rand(kr, shape).astype(utype) >> 2).astype(ring.dtype)
        _record_offline_mac("offline.trunc_pair", ring, 2 * numel(shape))
        return _share_mac(k1, r, ring), _share_mac(k2, r >> shift, ring)


# ---------------------------------------------------------------------------
# the backend
# ---------------------------------------------------------------------------

class SPDZ2PC(BackendDefaults):
    name = "spdz2pc"
    # leading-axis size of Share.sh: 2 value + 2 MAC rows. Everything
    # generic (abstract_shares, the executor's reshape, vmap) treats it
    # as an opaque component count.
    n_parties = 4
    # ... but the WIRE has exactly 2 physical parties: rows are p0/p1
    # value + p0/p1 MAC, and partial opens exchange value rows only
    # (BackendDefaults.open_msgs already routes rows 0<->1)
    n_wire_parties = 2
    # dealer MAC'd trunc pairs are exact at any shift/exponent, so the
    # scale lattice may defer up to the ring-wide 3f headroom cap
    exact_trunc = True

    # -- sharing --------------------------------------------------------
    def share_encoded(self, key: jax.Array, enc: jax.Array,
                      ring: RingSpec) -> jax.Array:
        return _share_mac(key, enc, ring)

    def from_public(self, enc: jax.Array) -> jax.Array:
        ring = _ring_of(enc)
        _, a0, a1 = mac_key(ring)
        z = jnp.zeros_like(enc)
        return jnp.stack([enc, z, a0 * enc, a1 * enc])

    def open_bytes(self, ring: RingSpec, n: int) -> int:
        # PARTIAL open: value components only — MACs stay secret
        return 2 * ring.elem_bytes * n

    # -- affine hooks (MAC rows are not value components) ---------------
    def reconstruct(self, sh: jax.Array) -> jax.Array:
        ring = _ring_of(sh)
        sh = _maybe_tamper(sh)
        v = sh[0] + sh[1]
        _note_open("open", v, sh[2] + sh[3], ring)
        return v

    def add_public_encoded(self, sh: jax.Array, enc: jax.Array) -> jax.Array:
        ring = _ring_of(sh)
        _, a0, a1 = mac_key(ring)
        b = jnp.broadcast_to(enc, sh.shape[1:])
        return jnp.stack([sh[0] + b, sh[1], sh[2] + a0 * b, sh[3] + a1 * b])

    # -- openings -------------------------------------------------------
    def _open_flight(self, op: str, tensors, ring: RingSpec, *, n: int,
                     flops: int = 0):
        """Partially open masked tensors in ONE flight (value components
        only — same wire bytes as semi-honest 2PC) and enqueue each
        tensor's MAC obligation for the batched boundary check."""
        with span("mpc.open"):
            wire_elems = sum(numel(t.shape[1:]) for t in tensors)
            comm.record(op, rounds=1,
                        nbytes=2 * ring.elem_bytes * wire_elems,
                        numel=n, flops=flops, tag="bw",
                        payload=[(p, 1 - p, t[p])
                                 for t in tensors for p in (0, 1)])
            out = []
            for t in tensors:
                t = _maybe_tamper(t)
                v = t[0] + t[1]
                _note_open(op, v, t[2] + t[3], ring)
                out.append(v)
            return tuple(out)

    # -- truncation -----------------------------------------------------
    def trunc(self, x, key: jax.Array | None, *, shift: int | None = None,
              site: str | None = None):
        """Dealer-assisted MAC'd truncation — BOTH rings.

        Local shifting is not MAC-preserving (alpha*(x >> s) has no
        local relation to (alpha*x) >> s) and would let a malicious
        party shift dishonestly, so the semi-honest RING64 free local
        path does not exist here: every forced truncation opens x + r
        (partially) and rebuilds from the dealer's MAC'd (r, r >> shift)
        pair. One opening round + 2 MAC'd tensors of offline bytes per
        force, any shift — the malicious overhead curve's RING64 story.
        """
        ring = x.ring
        if key is None:
            raise ValueError(
                "spdz2pc truncation requires a PRNG key: there is no "
                "MAC-preserving local-shift path (the engine threads a "
                "key through every force site)")
        shift = ring.frac_bits if shift is None else shift
        out_fb = x.fb - shift
        n = numel(x.shape)
        with span("mpc.trunc", site=site):
            rsh, rtsh = _mac_trunc_pair(key, x.shape, ring, shift)
            (m,) = self._open_flight("trunc_open", (x.sh + rsh,), ring, n=n)
            m_t = m >> shift
            _, a0, a1 = mac_key(ring)
            out = jnp.stack([m_t - rtsh[0], -rtsh[1],
                             a0 * m_t - rtsh[2], a1 * m_t - rtsh[3]])
            return x.with_scale(out, out_fb)

    # -- multiplication -------------------------------------------------
    def _sacrifice(self, op: str, ring: RingSpec, n_triple: int,
                   n_open: int, wire_elems: int) -> None:
        """Burn a second dealer triple to authenticate the first: the
        parties open t*a - a' (and the matching c-correlation) masked
        components — 1 fusible round, and the sacrificed triple's MAC'd
        bytes on the offline channel."""
        _record_offline_mac(f"offline.sacrifice_{op}", ring, n_triple)
        comm.record("sacrifice", rounds=1,
                    nbytes=2 * ring.elem_bytes * wire_elems,
                    numel=n_open, tag="bw")

    def mul(self, x, y, key: jax.Array):
        """Authenticated Beaver multiply: sacrifice flight + (eps, delta)
        partial open; MAC rows recombine with the split of alpha on the
        public eps*delta term. Raw product — `mpc/ops.py` owns scale."""
        ring = x.ring
        shape = jnp.broadcast_shapes(x.shape, y.shape)
        xb = jnp.broadcast_to(x.sh, (4,) + shape)
        yb = jnp.broadcast_to(y.sh, (4,) + shape)
        n = numel(shape)
        a4, b4, c4 = _mac_mul_triple(key, shape, ring)
        self._sacrifice("triple", ring, 3 * n, n, 2 * n)
        eps, dlt = self._open_flight("beaver_mul", (xb - a4, yb - b4), ring,
                                     n=n, flops=4 * n)
        with span("mpc.combine"):
            _, a0, a1 = mac_key(ring)
            ed = eps * dlt
            z = c4 + eps * b4 + dlt * a4
            z = z.at[0].add(ed)
            z = z.at[2].add(a0 * ed)
            z = z.at[3].add(a1 * ed)
            return x.with_sh(z)

    def matmul(self, x, y, key: jax.Array, *,
               combine_impl: str | None = None):
        """Authenticated Beaver matmul (same input-proportional wire
        profile as semi-honest 2PC, plus the sacrifice flight and the
        doubled MAC'd triple bytes offline). `combine_impl` is the
        semi-honest 2-row kernel knob and is ignored."""
        ring = x.ring
        a4, b4, c4 = _mac_matmul_triple(key, x.shape, y.shape, ring)
        na, nb = numel(x.shape), numel(y.shape)
        nc = numel(c4.shape[1:])
        self._sacrifice("matmul_triple", ring, na + nb + nc, na + nb,
                        na + nb)
        m, k = x.shape[-2], x.shape[-1]
        n_out = y.shape[-1]
        batch = numel(x.shape[:-2])
        eps, dlt = self._open_flight("beaver_matmul",
                                     (x.sh - a4, y.sh - b4), ring,
                                     n=na + nb,
                                     flops=2 * batch * m * k * n_out)
        with span("mpc.combine"):
            eb = jnp.matmul(jnp.broadcast_to(eps, (4,) + eps.shape), b4,
                            preferred_element_type=ring.dtype)
            ad = jnp.matmul(a4, jnp.broadcast_to(dlt, (4,) + dlt.shape),
                            preferred_element_type=ring.dtype)
            z = c4 + eb + ad
            ed = jnp.matmul(eps, dlt, preferred_element_type=ring.dtype)
            _, a0, a1 = mac_key(ring)
            z = z.at[0].add(ed)
            z = z.at[2].add(a0 * ed)
            z = z.at[3].add(a1 * ed)
            return x.with_sh(z)

    # -- the boundary check ---------------------------------------------
    def mac_check_flight(self, ring: RingSpec) -> None:
        """Batched MAC check at the forward's public boundary (invoked
        by `MPCEngine.entropy_head`). Constant-size regardless of how
        many values were opened — the parties commit-and-open ONE random
        linear combination of their sigma shares: 1 fusible bw round,
        plus the dealer's one-time MAC-key shipment (offline). When a
        `mac_scope` is ambient, the deferred obligations are verified
        here — a tampered execution aborts at its output."""
        comm.record("offline.mac_key", rounds=0,
                    nbytes=2 * ring.elem_bytes, numel=1, tag="offline")
        comm.record("mac_check", rounds=1, nbytes=4 * ring.elem_bytes,
                    numel=1, tag="bw")
        st = get_mac_state()
        if st is not None:
            st.verify()
