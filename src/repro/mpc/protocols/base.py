"""The protocol-backend interface every sharing scheme implements.

`Share` values carry their backend's name (`Share.proto`); the generic
layers (`mpc/ops`, `mpc/compare`, `mpc/nonlinear`, the engines) look the
backend up per value and delegate every scheme-dependent operation here.
Backends are stateless singletons — randomness always arrives as an
explicit PRNG key so executions stay reproducible across schedule
variants.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.mpc.ring import RingSpec


def numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


class BackendDefaults:
    """Default implementations of the affine share transforms that most
    schemes share: reconstruction is the plain sum over the leading
    axis, and a public constant lands on component 0 (`from_public`'s
    convention). Schemes whose leading axis carries NON-value rows — the
    MAC components of spdz2pc — override both: summing all rows there
    would yield value + alpha*value, and a constant must also update the
    MAC rows to keep the authenticated invariant.

    `n_wire_parties` is the number of PHYSICAL protocol parties on the
    wire — the process count `net.PartyRuntime` spawns. It equals
    `n_parties` for plain schemes but NOT for MAC'd ones (spdz2pc stacks
    4 share rows across 2 parties), which is why the wire layer must
    never size itself off the component axis.
    """

    n_wire_parties = 2

    # True when `trunc(shift=)` is EXACT at any shift and any carried
    # exponent (dealer pair / trunc2 subprotocol). Probabilistic local
    # truncation (additive2pc's RING64 shift, replicated3pc's
    # regrouping) wraps a share with probability ~ encoded/2**bits per
    # element — tolerable in the validated 2f regime, 2**f times worse
    # at a 3f exponent — so only exact-trunc backends may defer under
    # the ring-wide 3f headroom cap (ops._headroom_bits).
    exact_trunc = False

    def reconstruct(self, sh: jax.Array) -> jax.Array:
        out = sh[0]
        for i in range(1, sh.shape[0]):
            out = out + sh[i]
        return out

    def add_public_encoded(self, sh: jax.Array, enc: jax.Array) -> jax.Array:
        return sh.at[0].add(jnp.broadcast_to(enc, sh.shape[1:]))

    def open_msgs(self, sh: jax.Array):
        """The messages an opening of `sh` puts on the wire, as
        (src, dst, tensor) entries for `comm.record(payload=...)` —
        MUST serialize to exactly `open_bytes` bytes. Default: the
        2-party duplex exchange of value components (rows 0 and 1 —
        also correct for spdz2pc, whose partial opens send value rows
        only)."""
        return [(0, 1, sh[0]), (1, 0, sh[1])]

    def dealer_material(self, rng, op: str, ring: RingSpec, elems: int):
        """Synthesize `elems` ring elements of dealer (offline-channel)
        material for offline op `op` — the bytes a crypto provider
        streams ahead of the phase. Every offline record (Beaver/
        sacrifice triples, truncation pairs, MAC keys) already counts
        its TOTAL element footprint in `numel`, so one uniform draw of
        that many ring elements is shape-correct for all of them. The
        serve/ dealer pool pre-generates these on a worker thread;
        dealer-free schemes (replicated 3pc) never place an order.

        `rng` is a numpy Generator — pool material is pre-staged bytes,
        deliberately OUTSIDE the execution's jax PRNG stream (online
        values stay key-derived, so scores are driver-invariant)."""
        if elems <= 0:
            return None
        udt = {32: "uint32", 64: "uint64"}[ring.bits]
        buf = rng.integers(0, (1 << ring.bits) - 1, size=int(elems),
                           dtype=udt, endpoint=True)
        return buf.view(f"int{ring.bits}")


@runtime_checkable
class ProtocolBackend(Protocol):
    """Scheme-dependent share operations.

    `mul`/`matmul` consume and produce `sharing.Share` values and record
    their own wire flights (and, for dealer-based schemes, their offline
    bytes) into the ambient ledger; `trunc` implements the scheme's
    fixed-point truncation. Everything linear is protocol-generic and
    lives in `mpc/ops` — with two affine exceptions (`reconstruct`,
    `add_public_encoded`) that dispatch here because MAC'd schemes
    interpret their extra leading-axis rows differently.

    Backends targeting MALICIOUS security may additionally expose
    `mac_check_flight(ring)`: the engine calls it once at the forward's
    public boundary (`MPCEngine.entropy_head`) to price — and, when a
    verification scope is ambient, run — the batched MAC check.
    """

    name: str                     # registry key, also Share.proto
    n_parties: int                # leading party-axis size of Share.sh
    n_wire_parties: int           # physical parties on the wire (net/)

    def share_encoded(self, key: jax.Array, enc: jax.Array,
                      ring: RingSpec) -> jax.Array:
        """(n_parties, *enc.shape) uniform components summing to enc."""
        ...

    def from_public(self, enc: jax.Array) -> jax.Array:
        """Trivial sharing of a public ring element."""
        ...

    def open_bytes(self, ring: RingSpec, n: int) -> int:
        """Wire bytes for opening n ring elements (1 round)."""
        ...

    def reconstruct(self, sh: jax.Array) -> jax.Array:
        """Value from the stacked components (the functionality-boundary
        reconstruction; MAC'd schemes also enqueue a check obligation)."""
        ...

    def add_public_encoded(self, sh: jax.Array, enc: jax.Array) -> jax.Array:
        """Add an already-encoded public constant to the sharing."""
        ...

    def mul(self, x, y, key: jax.Array):
        """Elementwise secure multiply (broadcasting). Returns the RAW
        product — scale bookkeeping (the summed exponent, any forced
        input truncation) lives in `mpc/ops.py`."""
        ...

    def matmul(self, x, y, key: jax.Array, *,
               combine_impl: str | None = None):
        """Batched secure matmul (raw product; see `mul`)."""
        ...

    def trunc(self, x, key: jax.Array | None, *, shift: int | None = None,
              site: str | None = None):
        """Divide by 2**shift (default: frac_bits, one canonical scale)
        and lower the carried exponent accordingly — the generalized
        `trunc(shift=)` that resolves any accumulated excess in one op.
        `site` is meta on the `sf.mpc.trunc` span, where there is one."""
        ...
