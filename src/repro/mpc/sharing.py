"""Protocol-generic secret shares.

A `Share` stacks every party's share component on a leading axis whose
size the protocol backend decides (`mpc/protocols/`):

  2pc  additive 2-party:      sh[0] + sh[1] = value          (axis 2)
  3pc  replicated 2-of-3:     sh[0] + sh[1] + sh[2] = value  (axis 3),
       party i holds the pair (sh[i], sh[i+1 mod 3])

This layout is deliberate: on the multi-pod mesh the party axis is
sharded over the "pod" mesh axis, so each party's component physically
lives on its own pod and every `open` is an inter-pod collective. On a
single pod the components are co-located ("simulation mode"). Either
way the arithmetic is identical.

The share container itself is protocol-agnostic: it carries the ring,
the protocol name, AND the fixed-point scale it is currently encoded at
(`fb`, the carried frac-bits exponent of mpc/scale.py) — all three are
static pytree aux data. Every op that depends on the sharing scheme —
`share`, `open_`, multiplication, truncation — routes through the
backend registered under `proto`; every op that changes the scale
adjusts `fb`, so "this tensor still owes a truncation" is a tracked
property of the value instead of an implicit calling convention.
`open_` no longer hard-codes the 2-party wire model: bytes-on-wire come
from `backend.open_bytes`.

Opening is the one scale boundary that resolves for free: a revealed
ring element is public, so the receiver applies the exact division by
2**fb during decode — truncation protocols exist only because SECRET
values cannot be shifted exactly, and `reveal` therefore never forces
one (see ops.force for the consumers that must).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.mpc.ring import RingSpec, RING64
from repro.mpc import comm
from repro.spans import span


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Share:
    sh: jax.Array                 # (n_parties, *shape) ring ints
    ring: RingSpec                # static
    proto: str = "2pc"            # static: protocol backend name
    fb: int | None = None         # static: carried frac-bits exponent
                                  # (None normalizes to ring.frac_bits)

    def __post_init__(self):
        if self.fb is None:
            self.fb = self.ring.frac_bits

    # -- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return (self.sh,), (self.ring, self.proto, self.fb)

    @classmethod
    def tree_unflatten(cls, aux, children):
        ring, proto, fb = aux
        return cls(children[0], ring, proto, fb)

    # -- convenience ----------------------------------------------------
    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.sh.shape[1:])

    @property
    def ndim(self) -> int:
        return self.sh.ndim - 1

    @property
    def n_parties(self) -> int:
        return self.sh.shape[0]

    @property
    def excess(self) -> int:
        """Frac bits above canonical — the truncation this value owes."""
        return self.fb - self.ring.frac_bits

    @property
    def backend(self):
        from repro.mpc import protocols
        return protocols.get(self.proto)

    def with_sh(self, sh: jax.Array) -> "Share":
        """Same ring/protocol/SCALE, new share components — THE way to
        rebuild a share from a scale-preserving transform (a bare
        Share(sh, ring) would silently re-label 3PC shares as 2PC and
        re-stamp a 2f-scale tensor as canonical)."""
        return Share(sh, self.ring, self.proto, self.fb)

    def with_scale(self, sh: jax.Array, fb: int) -> "Share":
        """Rebuild at a different carried exponent (product emission,
        truncation, lifts)."""
        return Share(sh, self.ring, self.proto, fb)

    def derive(self, fn) -> "Share":
        """Scale-preserving LAYOUT transform (reshape/moveaxis/broadcast
        ...) that remembers its source: `ops.force` walks this lineage
        so a forced truncation fires once on the pre-layout tensor (at
        its smaller element count, for broadcasts) and the cheap layout
        replays on the truncated components."""
        out = self.with_sh(fn(self.sh))
        out._lineage = (self, fn)
        return out

    def __getitem__(self, idx) -> "Share":
        idx = idx if isinstance(idx, tuple) else (idx,)
        return self.with_sh(self.sh[(slice(None),) + idx])

    def reshape(self, *shape) -> "Share":
        return self.derive(
            lambda sh: sh.reshape((sh.shape[0],) + tuple(shape)))

    def astuple(self) -> tuple:
        return tuple(self.sh[i] for i in range(self.sh.shape[0]))


# Historic name — the additive-2PC container before protocols became
# pluggable. Every call site that builds one positionally still works
# (proto defaults to "2pc").
AShare = Share


def reconstruct(sh) -> jax.Array:
    """Functionality-boundary reconstruction.

    Pass a `Share` to dispatch to its backend — REQUIRED for schemes
    whose extra leading-axis rows are not value components (spdz2pc's
    MAC rows: summing all four rows would yield value + alpha*value),
    and what lets MAC'd backends enqueue the check obligation for every
    opened value. A raw stacked array still sums its rows (the legacy
    additive path, correct for 2pc/3pc component arrays)."""
    if isinstance(sh, Share):
        return sh.backend.reconstruct(sh.sh)
    out = sh[0]
    for i in range(1, sh.shape[0]):
        out = out + sh[i]
    return out


def share(key: jax.Array, x: jax.Array, ring: RingSpec = RING64,
          proto: str = "2pc") -> Share:
    """Encode x in the ring and split into uniform shares (backend
    layout: 2 additive components for 2pc, 3 replicated for 3pc)."""
    return share_encoded(key, ring.encode(x), ring, proto)


def share_encoded(key: jax.Array, enc: jax.Array, ring: RingSpec = RING64,
                  proto: str = "2pc", fb: int | None = None) -> Share:
    """Split an already-encoded ring tensor; `fb` tags the scale the
    encoding carries (comparison bits are shared at fb=0, making the
    b*(x-y) selection multiply exact and truncation-free)."""
    from repro.mpc import protocols
    with span("mpc.share"):
        return Share(protocols.get(proto).share_encoded(key, enc, ring),
                     ring, proto, fb)


def open_(x: Share, op: str = "open") -> jax.Array:
    """Reconstruct the ring element (each party sends the component(s)
    the others lack: 1 round, backend-defined bytes). The element is
    returned AT THE CARRIED SCALE (x.fb) — decode with
    `ring.decode_at(v, x.fb)`; once public, the scale resolves exactly
    for free. The record's payload is the backend's actual message set
    (`open_msgs`), so `--wire` runs serialize the real components."""
    comm.record(op, rounds=1, nbytes=x.backend.open_bytes(x.ring, _numel(x)),
                numel=_numel(x), tag="bw", payload=x.backend.open_msgs(x.sh))
    return reconstruct(x)


def reveal(x: Share) -> jax.Array:
    """Open and decode to float at the carried scale (exact — deferred
    truncation costs a revealed value nothing)."""
    return x.ring.decode_at(open_(x), x.fb)


def zeros_like(x: Share) -> Share:
    return x.with_sh(jnp.zeros_like(x.sh))


def from_public(v: jax.Array, ring: RingSpec = RING64,
                proto: str = "2pc", fb: int | None = None) -> Share:
    """A public constant as a (trivial) share: component 0 holds it all."""
    from repro.mpc import protocols
    fb = ring.frac_bits if fb is None else fb
    return Share(protocols.get(proto).from_public(ring.encode_at(v, fb)),
                 ring, proto, fb)


def _numel(x: Share) -> int:
    n = 1
    for d in x.shape:
        n *= int(d)
    return n
