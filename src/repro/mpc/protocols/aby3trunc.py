"""ABY3 exact truncation (trunc2) over replicated 2-of-3 sharing.

Identical to `replicated3pc` everywhere except `trunc`: the
probabilistic regrouped local shift — whose RING32 wrap risk is pinned
but real (wrap probability |v|/2**(bits-1) per element, measured by the
statistical test in tests/test_malicious.py) — is replaced by ABY3's
two-phase EXACT subprotocol:

  phase 1  the parties jointly generate replicated sharings of a random
           pair (r, r >> shift), r drawn from the safe range
           [0, 2**(bits-2)) — one resharing round of 2 tensors
           (correlated-PRNG generation + re-replication);
  phase 2  open the masked value m = x + r (3 messages), shift the now
           PUBLIC m exactly, and output <m >> shift> - <r >> shift>.

Phase 2 DEPENDS on phase 1's messages being received, so the two phases
can never share a flight: `trunc` emits ONE `trunc2` record of
rounds=2 — a multi-round record is exactly what the flight batcher
treats as a barrier (flush, then record eagerly), so fusion legality
falls out of the existing `FlightBatcher.absorb` rule with no new code.
Composes with the scale-carrying `trunc(shift=)` contract unchanged:
one subprotocol clears any accumulated excess, same cost for any shift.

Error is <= 1 ulp ALWAYS (the same dealer-pair bound as additive2pc's
RING32 path) — zero regrouping wraps, on both rings, which is the
correctness this backend buys for 2 rounds + 6 components of wire per
forced truncation where `replicated3pc` pays ~zero. Keyless boundary
truncs (no PRNG key) fall back to the parent's probabilistic regroup —
the engine threads keys through every force site, so the executed
forward never takes that path.

Still a semi-honest, honest-majority backend (exactness is a
correctness upgrade, not a malicious-security one) and still dealer
free: zero offline bytes, like its parent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.mpc import comm
from repro.mpc.protocols.base import numel
from repro.mpc.protocols.replicated3pc import Replicated3PC
from repro.spans import span


class ABY3Trunc(Replicated3PC):
    name = "aby3trunc"
    # trunc2 is exact at any shift/exponent, so the scale lattice may
    # defer up to the ring-wide 3f headroom cap (the keyless boundary
    # fallback is never on the executed forward path)
    exact_trunc = True

    def trunc(self, x, key: jax.Array | None, *, shift: int | None = None,
              site: str | None = None):
        """Two-phase exact truncation (see module docstring). One
        `trunc2` record: rounds=2 (pair generation, then the dependent
        masked open — a batcher barrier), bytes = 2 phases x 3 messages
        of one tensor each."""
        ring = x.ring
        shift = ring.frac_bits if shift is None else shift
        if key is None:
            # boundary-only fallback: probabilistic regroup (documented;
            # the engine always supplies keys on the executed path)
            return super().trunc(x, key, shift=shift, site=site)
        out_fb = x.fb - shift
        n = numel(x.shape)
        with span("mpc.trunc", site=site):
            kr, k1, k2 = jax.random.split(key, 3)
            utype = jnp.uint32 if ring.bits == 32 else jnp.uint64
            # r from the "safe" range [0, 2**(bits-2)) to avoid sign wrap
            r = (ring.rand(kr, x.shape).astype(utype) >> 2).astype(
                ring.dtype)
            r_t = r >> shift
            rsh = self.share_encoded(k1, r, ring)
            rtsh = self.share_encoded(k2, r_t, ring)
            with span("mpc.open"):
                masked = x.sh + rsh
                # wire payload, phased like the protocol: sub-round 0 is
                # the pair-generation resharing (one component per
                # party), sub-round 1 the dependent masked open
                # (neighbour sends the component party i lacks) — 2
                # phases x 3 messages = the priced 6 tensors
                comm.record("trunc2", rounds=2,
                            nbytes=6 * ring.elem_bytes * n, numel=n,
                            tag="bw",
                            payload=[(i, (i - 1) % 3, rsh[i], 0)
                                     for i in range(3)]
                            + [((i + 1) % 3, i, masked[(i + 2) % 3], 1)
                               for i in range(3)])
                m = masked[0] + masked[1] + masked[2]    # open x + r
            m_t = m >> shift                          # public exact shift
            out = jnp.stack([m_t - rtsh[0], -rtsh[1], -rtsh[2]])
            return x.with_scale(out, out_fb)
