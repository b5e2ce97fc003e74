"""Beaver-combine matmul on the int32 TPU ring (both parties fused).

Computes, exactly in wrapping int32 arithmetic (the ring Z_{2^32}):
  z_p = c_p + eps @ b_p + a_p @ dlt   (+ party0 only: eps @ dlt)

This is the per-party local step of a secure matmul after (eps, dlt) are
opened; it is the bandwidth-bound hot loop of the MPC selection phase.
Both products of a party ride ONE matmul over a doubled contraction,
  z_p = c_p + [eps | a_p] @ [b_p' ; dlt],   b_0' = b_0 + dlt, b_1' = b_1,
so party 0's extra eps @ dlt costs nothing.

Mosaic has no int32 matmul on the TPU, so each int32 operand is written
as four balanced signed 8-bit digits, x = sum_i d_i 2^(8i) (mod 2^32),
d_i in [-128, 127], and a product is sum_{i+j<=3} (d_i @ e_j) << 8(i+j):
ten int8 MXU dots with int32 accumulation (terms with i+j >= 4 vanish
mod 2^32). Per K-tile |d_i e_j| <= 2^14, so with bk <= 128 each dot sums
to under 2^21 and the shifted adds wrap only where the ring does — the
result is bitwise equal to kernels/ref.secure_matmul_combine.
Grid (party, M/bm, N/bn, 2K/bk), K innermost, int32 accumulator in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# x + 0x80808080 biases every byte by 128, so byte_i - 128 is the
# balanced digit (int32 two's complement of 0x80808080)
_BIAS = -0x7F7F7F80


def int8_digits(x: jax.Array) -> jax.Array:
    """(…) int32 -> (4, …) int8 balanced digits, x = sum d_i 2^(8i)."""
    u = x + jnp.int32(_BIAS)
    return jnp.stack([((u >> (8 * i)) & 0xFF) - 128
                      for i in range(4)]).astype(jnp.int8)


def _kernel(l_ref, r_ref, c_ref, z_ref, acc, *, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    d = [l_ref[0, i] for i in range(4)]
    e = [r_ref[0, j] for j in range(4)]
    z = acc[...]
    for s in range(4):
        # explicit DEFAULT precision: an ambient float32 matmul precision
        # would reach these integer dots, and Mosaic refuses an int8
        # matmul with fp32 contract precision
        p = sum(jnp.dot(d[i], e[s - i], precision=jax.lax.Precision.DEFAULT,
                        preferred_element_type=jnp.int32)
                for i in range(s + 1))
        z = z + (p << (8 * s))
    acc[...] = z

    @pl.when(ik == nk - 1)
    def _epilogue():
        z_ref[0] = acc[...] + c_ref[0]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _combine(eps, dlt, a_sh, b_sh, c_sh, *, bm: int, bn: int, bk: int,
             interpret: bool):
    """z_p = c_p + [eps | a_p] @ [b_p ; dlt] for every party p on the
    leading axis (party 0's b already carries + dlt)."""
    m, kdim = eps.shape
    n = dlt.shape[1]
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0
    assert bk <= 128, "int8 digit dots must stay under 2^21 per tile"
    parties = a_sh.shape[0]
    lhs = jnp.concatenate([jnp.broadcast_to(eps, a_sh.shape), a_sh], axis=2)
    rhs = jnp.concatenate([b_sh, jnp.broadcast_to(dlt, b_sh.shape)], axis=1)
    # the doubled contraction is tiled by bk (or taken whole when it is
    # shorter); zero columns/rows pad it to a whole number of tiles
    kc = 2 * kdim
    bk = min(bk, kc)
    pad = -kc % bk
    if pad:
        lhs = jnp.pad(lhs, ((0, 0), (0, 0), (0, pad)))
        rhs = jnp.pad(rhs, ((0, 0), (0, pad), (0, 0)))
    nk = (kc + pad) // bk
    # (P, 4, M, 2K) and (P, 4, 2K, N) int8 digit stacks
    ld = jnp.moveaxis(int8_digits(lhs), 0, 1)
    rd = jnp.moveaxis(int8_digits(rhs), 0, 1)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(parties, m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((1, 4, bm, bk), lambda p, im, in_, ik: (p, 0, im, ik)),
            pl.BlockSpec((1, 4, bk, bn), lambda p, im, in_, ik: (p, 0, ik, in_)),
            pl.BlockSpec((1, bm, bn), lambda p, im, in_, ik: (p, im, in_)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda p, im, in_, ik: (p, im, in_)),
        out_shape=jax.ShapeDtypeStruct((parties, m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(ld, rd, c_sh)


def secure_matmul(eps, dlt, a_sh, b_sh, c_sh, *, bm: int = 128,
                  bn: int = 128, bk: int = 128, interpret: bool = False,
                  mesh=None):
    """eps: (M, K), dlt: (K, N) opened int32; a_sh/b_sh/c_sh: (2, ...) share
    stacks. Returns z_sh (2, M, N) — both parties' combine in one launch,
    the party being the kernel's outermost grid axis.

    Mosaic kernels cannot be partitioned automatically: on a device
    `mesh` the kernel runs under `jax.shard_map`, each party's shares on
    the devices of its "pod" slice (replicated where the mesh has none)."""
    b_sh = b_sh.at[0].add(dlt)          # party 0's eps @ dlt rides eps @ b_0
    fn = functools.partial(_combine, bm=bm, bn=bn, bk=bk,
                           interpret=interpret)
    if mesh is None:
        return fn(eps, dlt, a_sh, b_sh, c_sh)
    party = P("pod") if "pod" in mesh.axis_names else P()
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(P(), P(), party, party, party),
                         out_specs=party, check_vma=False)(
        eps, dlt, a_sh, b_sh, c_sh)
