"""Dispatch wrappers: Pallas kernel on TPU, interpret/ref elsewhere.

`use_pallas()` — TPU backend gets compiled kernels; CPU gets either
interpret-mode kernels (tests: numerics of the kernel body itself) or
the jnp reference (fast path for examples). Callers can force either
via the `impl` argument ("pallas" | "interpret" | "ref" | "auto").
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.parallel import sharding
from repro.kernels.mlp_softmax_attn import mlp_softmax_attn as _msa
from repro.kernels.flash_attn import flash_attn as _fa
from repro.kernels.entropy_head import entropy_head as _eh
from repro.kernels.ssd import ssd_chunked as _ssd
from repro.kernels.rg_lru import rg_lru_scan as _lru
from repro.kernels.secure_matmul import secure_matmul as _smm


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if on_tpu() else "ref"


def mlp_softmax_attn(q, k, v, w1, b1, w2, b2, *, impl="auto", **kw):
    m = _mode(impl)
    if m == "ref":
        return _ref.mlp_softmax_attn(q, k, v, w1, b1, w2, b2)
    return _msa(q, k, v, w1, b1, w2, b2, interpret=(m == "interpret"), **kw)


def flash_attn(q, k, v, *, causal=True, impl="auto", **kw):
    m = _mode(impl)
    if m == "ref":
        return _ref.flash_attn(q, k, v, causal=causal)
    return _fa(q, k, v, causal=causal, interpret=(m == "interpret"), **kw)


def entropy_head(logits, *, impl="auto", **kw):
    m = _mode(impl)
    if m == "ref":
        return _ref.entropy_head(logits)
    return _eh(logits, interpret=(m == "interpret"), **kw)


def ssd_chunked(x, a, b, c, *, chunk=128, impl="auto", **kw):
    """x: (B, T, H, P), a: (B, T, H), b/c: (B, T, N) — layout adapter
    around the kernel's (B, H, nc, Q, ...) arrangement."""
    m = _mode(impl)
    if m == "ref":
        return _ref.ssd(x, a, b, c)
    bs, t, h, p = x.shape
    q = min(chunk, t)
    assert t % q == 0
    nc = t // q
    xk = jnp.moveaxis(x.reshape(bs, nc, q, h, p), 3, 1)       # B H nc Q P
    ak = jnp.moveaxis(a.reshape(bs, nc, q, h), 3, 1)          # B H nc Q
    bk = b.reshape(bs, nc, q, -1)
    ck = c.reshape(bs, nc, q, -1)
    y = _ssd(xk, ak, bk, ck, interpret=(m == "interpret"), **kw)
    return jnp.moveaxis(y, 1, 3).reshape(bs, t, h, p)


def rg_lru_scan(a, b, *, impl="auto", **kw):
    m = _mode(impl)
    if m == "ref":
        return _ref.rg_lru(a, b)
    return _lru(a, b, interpret=(m == "interpret"), **kw)


def _tileable(dim: int, blk: int) -> bool:
    """The kernel shrinks each block to min(blk, dim) and requires the
    result to divide dim exactly."""
    return dim % min(blk, dim) == 0


def _pad_target(dim: int, blk: int) -> int:
    """Smallest tileable dim >= dim: a multiple of blk (dims <= blk are
    already tileable at block min(blk, dim)). Bounded < 2x per dim."""
    return dim if _tileable(dim, blk) else -(-dim // blk) * blk


_log = logging.getLogger(__name__)
_fallback_warned = False

# kernel-vs-ref dispatch counters (trace-time): the executor snapshots
# these around a phase to witness that fused RING32 combines actually
# ran through the kernel, not the silent ref fallback
_smm_stats = {"kernel": 0, "ref": 0, "padded": 0}


def smm_stats() -> dict:
    """Snapshot of the secure_matmul dispatch counters."""
    return dict(_smm_stats)


def reset_smm_stats() -> None:
    for k in _smm_stats:
        _smm_stats[k] = 0


def _warn_fallback(shape) -> None:
    global _fallback_warned
    if not _fallback_warned:
        _fallback_warned = True
        _log.warning(
            "secure_matmul: non-tileable shape %s fell back to the jnp "
            "reference combine (pad=False). Results are bitwise "
            "identical, but this shape is NOT running the kernel — "
            "pass pad=True (default) to pad-to-tile instead. "
            "(Further fallbacks are counted in smm_stats(), not logged.)",
            tuple(shape))


def _partition_mesh():
    """The ambient executor mesh when this call is not yet per device
    (outside any shard_map body): the kernel must be shard_mapped over
    it, since a Mosaic call cannot be partitioned automatically."""
    r = sharding.current_rules()
    if r is None or r.mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return r.mesh


def secure_matmul(eps, dlt, a_sh, b_sh, c_sh, *, impl="auto", pad=True,
                  **kw):
    """Beaver post-open combine, both parties fused (MPC hot path).

    Output dims (M, N) that do not tile are zero-PADDED to the next
    block multiple by default — exact in wrapping int32 ring arithmetic
    (zero rows/cols contribute zero to every product term and the padded
    output region is sliced away), so smoke geometries exercise the
    kernel instead of silently dropping to the reference; the kernel
    pads its own contraction. `pad=False` restores the old behaviour:
    fall back to the jnp reference, logged once per process and counted
    in `smm_stats()` so silent-cap drops stay visible.
    """
    m = _mode(impl)
    mm, kk = eps.shape
    nn = dlt.shape[1]
    blocks = (kw.get("bm", 128), kw.get("bn", 128))
    dims = (mm, nn)
    tiled = all(_tileable(d, blk) for d, blk in zip(dims, blocks))
    if m != "ref" and not tiled and not pad:
        _warn_fallback((mm, kk, nn))
        m = "ref"
    if m == "ref":
        _smm_stats["ref"] += 1
        return jnp.stack([
            _ref.secure_matmul_combine(eps, dlt, a_sh[0], b_sh[0], c_sh[0], 0),
            _ref.secure_matmul_combine(eps, dlt, a_sh[1], b_sh[1], c_sh[1], 1),
        ])
    if not tiled:
        pm, pn = (_pad_target(d, blk) for d, blk in zip(dims, blocks))
        eps = jnp.pad(eps, ((0, pm - mm), (0, 0)))
        dlt = jnp.pad(dlt, ((0, 0), (0, pn - nn)))
        a_sh = jnp.pad(a_sh, ((0, 0), (0, pm - mm), (0, 0)))
        b_sh = jnp.pad(b_sh, ((0, 0), (0, 0), (0, pn - nn)))
        c_sh = jnp.pad(c_sh, ((0, 0), (0, pm - mm), (0, pn - nn)))
        _smm_stats["padded"] += 1
    _smm_stats["kernel"] += 1
    z = _smm(eps, dlt, a_sh, b_sh, c_sh, interpret=(m == "interpret"),
             mesh=_partition_mesh(), **kw)
    if not tiled:
        z = z[:, :mm, :nn]
    return z
