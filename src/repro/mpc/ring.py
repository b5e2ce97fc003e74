"""Fixed-point ring specifications for the MPC substrate.

Values x in R are encoded as round(x * 2**frac_bits) in Z_{2**bits}, stored
in two's-complement signed integers (XLA integer arithmetic is modular, so
jnp +/-/* implement ring arithmetic directly).

Two presets:
  RING64  int64, 16 fractional bits — CrypTen's ring; used as the CPU
          correctness oracle (requires jax.enable_x64 scope).
  RING32  int32, 12 fractional bits — the TPU-native ring (MXU has no
          int64 path). Products of values |x·y| < 2**6 truncate locally
          with wrap probability < 2**-2 per element, so RING32 uses
          dealer-assisted truncation (SecureML-style) which is exact up
          to ±1 LSB. See ops.trunc.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RingSpec:
    name: str
    dtype: jnp.dtype
    bits: int
    frac_bits: int

    @property
    def scale(self) -> int:
        return 1 << self.frac_bits

    @property
    def elem_bytes(self) -> int:
        return self.bits // 8

    def encode(self, x: jax.Array) -> jax.Array:
        """float -> ring element at the canonical scale."""
        return self.encode_at(x, self.frac_bits)

    def encode_at(self, x: jax.Array, fb: int) -> jax.Array:
        """float -> ring element carrying `fb` fractional bits (the
        scale-carrying shares of mpc/scale.py; fb may exceed frac_bits
        or be negative)."""
        ftype = jnp.float64 if self.bits == 64 else jnp.float32
        return jnp.round(jnp.asarray(x, ftype)
                         * ftype(2.0) ** fb).astype(self.dtype)

    def decode(self, r: jax.Array) -> jax.Array:
        """ring element -> float (canonical scale)."""
        return self.decode_at(r, self.frac_bits)

    def decode_at(self, r: jax.Array, fb: int) -> jax.Array:
        """ring element carrying `fb` fractional bits -> float."""
        ftype = jnp.float64 if self.bits == 64 else jnp.float32
        return r.astype(ftype) / ftype(2.0) ** fb

    def rand(self, key: jax.Array, shape) -> jax.Array:
        """Uniform random ring element (a fresh additive mask)."""
        if self.bits == 64:
            lo = jax.random.randint(key, shape, 0, 1 << 32, dtype=jnp.uint32)
            k2 = jax.random.fold_in(key, 1)
            hi = jax.random.randint(k2, shape, 0, 1 << 32, dtype=jnp.uint32)
            full = hi.astype(jnp.uint64) << 32 | lo.astype(jnp.uint64)
            return full.astype(self.dtype)
        bits = jax.random.bits(key, shape, dtype=jnp.uint32)
        return bits.astype(self.dtype)


RING64 = RingSpec("ring64", jnp.int64, 64, 16)
RING32 = RingSpec("ring32", jnp.int32, 32, 12)


def x64_scope():
    """Context manager enabling 64-bit jnp types — RING64 arithmetic (and
    any op on its int64 shares, e.g. comparisons in QuickSelect) must run
    inside this scope or XLA demotes results to 32 bits."""
    return jax.enable_x64(True)
