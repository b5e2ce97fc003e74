"""Pluggable secret-sharing protocol backends.

The MPC substrate used to assume 2-party additive sharing with a
trusted dealer everywhere — share layout, Beaver triples, truncation
pairs, the `2 * elem_bytes` opening wire model were baked into every
file. This package makes the scheme a backend:

  additive2pc   semi-honest 2PC, CrypTen trust model: a trusted dealer
                (crypto provider) ships Beaver triples and truncation
                pairs ahead of time — their bytes land in the ledger's
                OFFLINE channel (tag="offline", priced separately from
                the online wire).
  replicated3pc honest-majority 3PC, 2-out-of-3 replicated sharing
                (ABY3-style): multiplication is local cross-terms plus
                a correlated-PRNG zero-share resharing flight, and
                truncation is probabilistic and local — NO dealer, zero
                offline bytes.
  spdz2pc       MALICIOUS-security 2PC: SPDZ-style MAC'd additive
                shares (4 leading-axis rows: value + MAC components),
                sacrifice-authenticated Beaver triples, partial opens
                with a batched boundary MAC check, dealer truncation on
                BOTH rings — tampering aborts (MacCheckError).
  aby3trunc     replicated3pc with ABY3's EXACT two-phase `trunc2` in
                place of the probabilistic regrouped shift: <= 1 ulp
                always, zero wraps, 2 rounds per forced truncation.

A backend owns exactly the operations where the schemes differ:

  n_parties      leading component-axis size of every `Share` (4 for
                 spdz2pc: 2 value + 2 MAC rows)
  share_encoded  layout of a fresh sharing (uniform components)
  from_public    trivial sharing of a public ring element
  open_bytes     wire bytes to open n elements
  reconstruct    value from stacked components (MAC'd schemes also
                 enqueue a check obligation)
  add_public_encoded  affine constant injection (MAC rows update too)
  mul / matmul   ring multiplication incl. its wire flights
  trunc          fixed-point truncation after a product

Flight legality is per-backend: additive-2PC openings fuse under the
deferred-reconstruction convention (messages are mask components,
public corrections applied after the flight; see mpc/fusion.py), and
replicated-3PC resharing messages are locally computable before their
flight departs, so independent groups batch the same way. Both mark
their flights tag="bw"; the batcher needs no scheme-specific code.

Everything above this layer (`ops`, `compare`, `nonlinear`, the
engines, the executor, the cost probe) is protocol-generic and
routes through `get(name)`.
"""
from __future__ import annotations

from repro.mpc.protocols.base import BackendDefaults, ProtocolBackend
from repro.mpc.protocols.additive2pc import Additive2PC
from repro.mpc.protocols.replicated3pc import Replicated3PC
from repro.mpc.protocols.spdz2pc import SPDZ2PC
from repro.mpc.protocols.aby3trunc import ABY3Trunc

PROTOCOLS: dict[str, ProtocolBackend] = {
    "2pc": Additive2PC(),
    "3pc": Replicated3PC(),
    "spdz2pc": SPDZ2PC(),
    "aby3trunc": ABY3Trunc(),
}


def get(name: str) -> ProtocolBackend:
    try:
        return PROTOCOLS[name]
    except KeyError:
        raise ValueError(
            f"unknown protocol {name!r} (expected one of "
            f"{sorted(PROTOCOLS)})") from None


__all__ = ["ProtocolBackend", "BackendDefaults", "Additive2PC",
           "Replicated3PC", "SPDZ2PC", "ABY3Trunc", "PROTOCOLS", "get"]
