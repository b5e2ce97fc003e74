"""Replicated 2-out-of-3 sharing — honest-majority 3PC, NO dealer.

Share layout: three uniform additive components on the leading axis,
`sh[0] + sh[1] + sh[2] = value` (mod 2**bits); party i holds the PAIR
(sh[i], sh[i+1 mod 3]) — the ABY3/Araki-et-al. replicated sharing that
privacy-preserving feature selection deploys in practice. Any single
party sees two uniform components and learns nothing; any two parties
can reconstruct.

Multiplication is dealer-free: party i computes the local cross-terms
its pair covers,

    z_i = x_i*y_i + x_i*y_{i+1} + x_{i+1}*y_i + alpha_i,

where (alpha_0, alpha_1, alpha_2) is a ZERO sharing from the correlated
PRNG (party i and i+1 share seed k_{i+1}; alpha_i = F_{k_i} - F_{k_{i+1}}
sums to 0 and costs no interaction). The z_i already form a valid
additive 3-sharing of x*y; ONE resharing flight (party i sends z_i to
party i-1) restores replication: 1 round, 3 messages of the OUTPUT's
elements — note the wire cost scales with the output, not the inputs,
the opposite of Beaver-matmul's (|x|+|y|) profile.

Truncation is probabilistic and local (zero rounds, zero offline
bytes, any shift): regroup the three components as the 2-of-2 sharing
(sh[0]+sh[1], sh[2]) — party 1 holds the first sum, parties 2 and 3
both hold sh[2] — apply the SecureML local-shift trick to that pair
(correct to ±1 LSB w.p. 1 - |v|/2**(bits-1)), and re-randomize the
result back into three components with the correlated PRNG. The
re-replication message that restores the 2-of-3 pair invariant rides
the next resharing flight (ABY3 fuses truncation into
multiplication's resharing) — zero extra rounds, but its bytes ARE
priced: `trunc` emits a 0-round `trunc_reshare` bw record of one
output component, folded into the enclosing fused flight by the
batcher and mirrored by `costs.trunc_cost(protocol="3pc")`.

There are NO offline records in this backend — `Ledger.offline_nbytes`
of any pure-3PC execution is exactly 0, which is the headline advantage
over the dealer-based additive2pc backend.

Flight legality: a resharing message z_i is locally computable before
its flight departs, so all reshares of an independent group (e.g. the
q/k/v projections) legally ride one fused flight; chains inside an
`eng.fused` group follow the same accounting convention as 2PC's
deferred reconstructions (mpc/fusion.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.mpc.ring import RingSpec
from repro.mpc import comm
from repro.mpc.protocols.base import BackendDefaults, numel
from repro.spans import span


class Replicated3PC(BackendDefaults):
    name = "3pc"
    n_parties = 3
    n_wire_parties = 3

    # -- sharing --------------------------------------------------------
    def share_encoded(self, key: jax.Array, enc: jax.Array,
                      ring: RingSpec) -> jax.Array:
        r0 = ring.rand(key, enc.shape)
        r1 = ring.rand(jax.random.fold_in(key, 101), enc.shape)
        return jnp.stack([r0, r1, enc - r0 - r1])

    def from_public(self, enc: jax.Array) -> jax.Array:
        z = jnp.zeros_like(enc)
        return jnp.stack([enc, z, z])

    def open_bytes(self, ring: RingSpec, n: int) -> int:
        # party i lacks component i+2; one neighbour sends it: 3 messages
        return 3 * ring.elem_bytes * n

    def open_msgs(self, sh: jax.Array):
        # party i holds pair (i, i+1) and lacks component i+2, which its
        # neighbour i+1 (holder of (i+1, i+2)) sends — the 3 messages
        # open_bytes prices
        return [((i + 1) % 3, i, sh[(i + 2) % 3]) for i in range(3)]

    # -- correlated-PRNG zero sharing -----------------------------------
    def _zero_share(self, key: jax.Array, shape, ring: RingSpec) -> jax.Array:
        """(3, *shape) components summing to 0: alpha_i = r_i - r_{i+1}
        where r_i comes from the seed parties i and i-1 share."""
        r = jnp.stack([ring.rand(jax.random.fold_in(key, 300 + i), shape)
                       for i in range(3)])
        return r - jnp.roll(r, -1, axis=0)

    # -- truncation -----------------------------------------------------
    def trunc(self, x, key: jax.Array | None, *, shift: int | None = None,
              site: str | None = None):
        """Probabilistic local truncation via the 2-of-2 regrouping —
        both rings, zero rounds, zero dealer bytes, any shift. On the
        TPU ring this trades additive2pc's exact dealer pair for a
        |v|/2**(bits-1) per-element wrap probability; RING64 keeps the
        same guarantee as 2PC local truncation.

        The regrouped result is re-randomized back into three components
        and RE-REPLICATED: party 1's fresh component must reach party 0
        to restore the 2-of-3 pair invariant. ABY3 folds that message
        into the next multiplication's resharing flight, so it costs no
        extra round — but it is NOT free bytes: one component of the
        output rides the wire, recorded here as a 0-round bw record the
        flight batcher folds into the enclosing fused flight (the
        ROADMAP PR 4 follow-up; previously modeled as free). Keyless
        boundary truncs skip re-randomization and the message."""
        ring = x.ring
        shift = ring.frac_bits if shift is None else shift
        out_fb = x.fb - shift
        hi = (x.sh[0] + x.sh[1]) >> shift
        lo = -((-x.sh[2]) >> shift)
        if key is None:
            return x.with_scale(jnp.stack([hi, jnp.zeros_like(hi), lo]),
                                out_fb)
        with span("mpc.trunc", site=site):
            r = ring.rand(key, hi.shape)
            n = numel(x.shape)
            # the re-replication message: party 1's fresh component r
            # reaches party 0 to restore the 2-of-3 pair invariant
            comm.record("trunc_reshare", rounds=0,
                        nbytes=ring.elem_bytes * n, numel=n, tag="bw",
                        payload=[(1, 0, r)])
            return x.with_scale(jnp.stack([hi - r, r, lo]), out_fb)

    # -- multiplication -------------------------------------------------
    def _cross_terms(self, xs: jax.Array, ys: jax.Array, key: jax.Array,
                     ring: RingSpec, mm: bool) -> jax.Array:
        x_n = jnp.roll(xs, -1, axis=0)
        y_n = jnp.roll(ys, -1, axis=0)
        if mm:
            z = (jnp.matmul(xs, ys, preferred_element_type=ring.dtype)
                 + jnp.matmul(xs, y_n, preferred_element_type=ring.dtype)
                 + jnp.matmul(x_n, ys, preferred_element_type=ring.dtype))
        else:
            z = xs * ys + xs * y_n + x_n * ys
        return z + self._zero_share(key, z.shape[1:], ring)

    def mul(self, x, y, key: jax.Array):
        """Elementwise multiply: local cross-terms + one resharing
        flight (no triple, no opening). Raw product — scale bookkeeping
        lives in `mpc/ops.py`."""
        ring = x.ring
        shape = jnp.broadcast_shapes(x.shape, y.shape)
        with span("mpc.reshare"):
            xb = jnp.broadcast_to(x.sh, (3,) + shape)
            yb = jnp.broadcast_to(y.sh, (3,) + shape)
            z = self._cross_terms(xb, yb, jax.random.fold_in(key, 1), ring,
                                  mm=False)
            n = numel(shape)
            comm.record("reshare_mul", rounds=1,
                        nbytes=3 * ring.elem_bytes * n, numel=n,
                        flops=6 * n, tag="bw",
                        payload=[(i, (i - 1) % 3, z[i]) for i in range(3)])
            return x.with_sh(z)

    def matmul(self, x, y, key: jax.Array, *,
               combine_impl: str | None = None):
        """Batched matmul: three local matmuls per party + one resharing
        flight of the OUTPUT (bytes ~ batch*m*n, vs 2PC's |x|+|y|).
        `combine_impl` is a 2PC Beaver-combine knob and is ignored."""
        ring = x.ring
        with span("mpc.reshare"):
            z = self._cross_terms(x.sh, y.sh, jax.random.fold_in(key, 1),
                                  ring, mm=True)
            m, k = x.shape[-2], x.shape[-1]
            n_out = y.shape[-1]
            batch = numel(z.shape[1:-2])
            n = batch * m * n_out
            comm.record("reshare_matmul", rounds=1,
                        nbytes=3 * ring.elem_bytes * n, numel=n,
                        flops=6 * batch * m * k * n_out, tag="bw",
                        payload=[(i, (i - 1) % 3, z[i]) for i in range(3)])
            return x.with_sh(z)
