"""The fixed-point scale lattice — one algebra for execution AND mirror.

A scale-carrying share encodes value v as round(v * 2**fb) where `fb`
(the carried frac-bits exponent, `Share.fb`) is static pytree aux data.
Canonical scale is the ring's `frac_bits` (f); a product of f-scale
operands sits at 2f, and instead of forcing a truncation at every op
boundary (the PR 3 `PendingShare` regime) the exponent simply flows
through downstream ops:

  lift        (exact, local, free)   int * 2**k        fb += k
  pow2 fold   (exact, local, free)   reinterpretation  fb -= k  for * 2**k
  trunc       (a protocol op)        int >> shift      fb -= shift

The lattice cap is RING-PARAMETERIZED: any op that GROWS integer
magnitude (lifting an operand for alignment, multiplying two shares)
must keep the result's exponent at or below the cap so
|v1*v2| < 2**(bits-1-cap) — the same headroom contract eager truncation
maintained. The default cap is 2f; rings wide enough to hold a third
fraction (3f < bits-1, i.e. RING64's 48 < 63) legally defer one level
deeper — a 2f product multiplying an f operand emits at 3f with NO
force, erasing the residual RING64 truncations the uniform 2f cap
paid. RING32 (3*12 = 36 > 31) stays at 2f. Callers opt in by passing
the ring's bit width (`bits=`); the bare 3-argument form keeps the
uniform 2f cap. Pure reinterpretations (pow2 folds) may push fb beyond
the cap because the integers never move; the next magnitude-growing
consumer truncates by the accumulated excess in one shot.

Reductions grow magnitude too: a sum of n terms at exponent s can
reach n times the largest term, so it needs ceil(log2 n) more bits of
headroom than its terms (`sum_plan`). Means divide by n as
2**-k * (1/m) with m odd (`split_reciprocal`): the power of two folds
for free and only 1/m is encoded, never a 1/n that rounds badly at f
bits (1/768 at 12 bits is 5/4096, 6.25% low; 1/3 is 1365/4096, 0.02%
low).

This module is the decision procedure only — pure functions of static
exponents, shared verbatim by the executable ops (`mpc/ops.py`) and the
analytic mirror (`mpc/costs.proxy_exec_cost`), so "where does a forced
truncation fire" exists exactly once and the record-for-record mirror
tests catch any drift.
"""
from __future__ import annotations

import math


def cap(f: int, bits: int | None = None) -> int:
    """Max exponent a magnitude-growing op may produce.

    2f by default; 3f when the ring's bit width is given and a third
    fraction still leaves sign + headroom (3f < bits - 1): RING64
    (f=16, bits=64) caps at 48, RING32 (f=12, bits=32) stays at 24.

    Callers gate `bits` on the backend's truncation exactness
    (`ops._headroom_bits` / the costs.py mirror): probabilistic local
    truncation wraps w.p. ~ encoded/2**bits, which a 3f exponent
    amplifies 2**f-fold, so only exact-trunc backends (spdz2pc,
    aby3trunc) pass their ring width here — everyone else passes None
    and keeps the validated 2f regime.
    """
    if bits is not None and 3 * f < bits - 1:
        return 3 * f
    return 2 * f


def pow2_exponent(v) -> int | None:
    """k such that v == ±2**k for a python/numpy scalar, else None.

    Multiplying by ±2**k is a pure exponent adjustment (fb -= k) plus at
    most a negation — zero arithmetic on the fraction, zero rounding,
    zero truncation. Non-scalars and non-powers return None (the general
    encode-at-f path)."""
    try:
        x = float(v)
    except (TypeError, ValueError):
        return None
    if x == 0.0 or math.isinf(x) or math.isnan(x):
        return None
    m, e = math.frexp(abs(x))       # |x| = m * 2**e, m in [0.5, 1)
    return e - 1 if m == 0.5 else None


def align_target(sa: int, sb: int, f: int, bits: int | None = None) -> int:
    """Common exponent for add/sub/concat operands at exponents sa, sb.

    Equal scales pass through (even above the cap: adding two
    reinterpreted tensors moves no integers). Otherwise the lower
    operand LIFTS to the higher exponent — exact and free — capped by
    `cap(f, bits)`: a lift beyond the cap would overflow the headroom
    contract, so the higher operand truncs down to the cap instead."""
    if sa == sb:
        return sa
    return min(max(sa, sb), cap(f, bits))


def mul_plan(sx: int, sy: int, f: int,
             bits: int | None = None) -> tuple[int, int, int]:
    """(shift_x, shift_y, out_exponent) for a share*share product.

    The product's exponent is sx + sy; while that exceeds the headroom
    cap, the larger operand is truncated — by exactly the excess when
    that suffices, never below canonical f. Two f-scale inputs emit at
    2f untruncated; a cap-scale input against an exponent-0 input (a
    comparison bit) multiplies for free. Under the 2f cap, 2f x f and
    2f x 2f force the carried truncation that eager mode paid
    per-product; under a ring-wide 3f cap (RING64) the 2f x f case
    emits at 3f force-free."""
    c = cap(f, bits)
    s = [sx, sy]
    shift = [0, 0]
    while s[0] + s[1] > c:
        i = 0 if s[0] >= s[1] else 1
        if s[i] <= f:
            break                   # both canonical: 2f is legal by cap
        red = s[0] + s[1] - c
        if s[0] == s[1]:
            # equal operands split the excess SYMMETRICALLY (the loop
            # pass reduces each side by half) — a squared operand
            # (x is y in ops._forced_operands) forces once and reuses,
            # which is only coherent when shift_x == shift_y
            red = -(-red // 2)
        red = min(s[i] - f, red)
        shift[i] += red
        s[i] -= red
    return shift[0], shift[1], s[0] + s[1]


def mul_public_plan(s: int, v, f: int,
                    bits: int | None = None) -> tuple[int | None, int, int]:
    """(fold_exponent, force_shift, out_exponent) for share * public v.

    Power-of-two scalars fold into the exponent (fold_exponent = k,
    force_shift = 0, out = s - k). General constants encode at f and
    multiply: if the product s + f would pass the cap, the input forces
    down by `force_shift` first — exactly to the exponent where the
    product lands on the cap (canonical under 2f; up to 2f input under
    a ring-wide 3f cap)."""
    k = pow2_exponent(v)
    if k is not None:
        return k, 0, s - k
    shift = max(0, s - (cap(f, bits) - f))   # product lands on the cap
    return None, shift, (s - shift) + f


# log2 of the bound on the mean magnitude of the terms a reduction sums:
# |sum of n terms| <= n * 2**SUM_ALLOWANCE. The program's reductions are
# LayerNorm statistics (the mean of the residual stream and the mean of
# its centred squares) and the pooled residual stream. Their size is set
# by the weights, not by the embedding's scale alone: attention outputs
# pile onto the residual stream, and on random proxies at hidden 768 the
# pooled stream reaches about 15 where the embeddings alone give 0.55.
# So the bound is the lattice's own: a dealer truncation draws its mask
# from [0, 2**(bits - 2)) and is exact only on values below 2**(bits - 2)
# (`protocols/additive2pc.trunc_pair`), so a product at the 2f cap that
# a truncation consumes holds magnitudes below 2**(bits - 2 - 2f) = 2**6
# on RING32, and so does a mean of such terms; the rule adds no bound of
# its own to the ring's. A term that averages above 2**SUM_ALLOWANCE may
# wrap the sum.
SUM_ALLOWANCE = 6


def sum_plan(s: int, n: int, f: int, bits: int) -> int:
    """Shift that forces the input of an n-term sum at exponent s down
    before the sum, on a ring of `bits` bits.

    The sum of n terms averaging below 2**SUM_ALLOWANCE in magnitude,
    encoded at s, stays below 2**(s + ceil(log2 n) + SUM_ALLOWANCE). A
    mean's integers are its sum's, and a truncation consumes them (the
    odd reciprocal's, or a later consumer's), so the sum must stay in
    the truncation's range, 2**(bits - 2). Where it would not, the input
    truncates by exactly the excess, never below canonical f: a
    canonical input is never forced, so its sum is held to
    2**(bits - 2 - f), 2**18 on RING32 (768 terms averaging 341). A
    function of shapes and the ring only, never of data: RING32 (f=12)
    forces a 2f input summed over 768 terms from 24 to 14 bits, over
    128 terms to 17; RING64 forces nothing at 2f up to n = 2**24, and a
    3f input over 768 terms by 2 bits."""
    grow = (n - 1).bit_length()          # ceil(log2 n)
    excess = s + grow + SUM_ALLOWANCE - (bits - 2)
    return max(0, min(excess, s - f))


def split_reciprocal(n: int) -> tuple[int, int]:
    """(k, m) with n = 2**k * m and m odd, so 1/n = 2**-k * (1/m).

    The 2**-k folds into the exponent for free (`pow2_exponent`); only
    1/m is encoded at f bits, off by at most 2**-(f+1), a relative error
    of at most m * 2**-(f+1) (m = 3 on RING32: 0.037%; 1365/4096 is
    0.024% low).
    m == 1 for a power of two: the whole mean is a fold."""
    if n < 1:
        raise ValueError(f"cannot split 1/{n}")
    k = (n & -n).bit_length() - 1
    return k, n >> k
