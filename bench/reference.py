"""Plain float reference of the proxy's entropy score, and its control.

The proxy of SelectFormer section 4.2 as the sieve scores it: token
embedding times sqrt(d_model); `n_layers` blocks of [LayerNorm whose
reciprocal square root is an MLP emulator, attention over the first
`n_heads` heads whose softmax is an MLP emulator, residual]; mean pool;
class head; an MLP emulator of softmax + entropy. Each emulator is
Linear -> ReLU -> Linear. Written here once, in straightforward
jax.numpy at float32 with every matmul at precision HIGHEST; it imports
nothing of the program and is handed only weights and tokens.

The control computes the same function one precision step below the
precision the configuration states. The program computes in RING32:
int32 words with 12 fractional bits. The step below is a 16-bit
fixed-point word with the same share of fractional bits (6). The
control rounds every value to that grid and wraps it to the word's
range. A bfloat16 run is also provided for the record. It is no step
of the fixed-point ladder: near the entropies' ln 2 its spacing, 2^-8,
is coarser than RING32's 2^-12, yet its gap can come out below the
program's wherever the program's own error (truncation, compounding
through the emulators) exceeds RING32's spacing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FX16_FRAC = 6
_HI = jax.lax.Precision.HIGHEST


def _exact(x):
    return x


def _fx16(x):
    s = 2.0 ** FX16_FRAC
    r = jnp.round(x.astype(jnp.float32) * s)
    return (jnp.mod(r + 2.0 ** 15, 2.0 ** 16) - 2.0 ** 15) / s


QUANT = {"float32": _exact, "fx16": _fx16}


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _mlp(p, x, q):
    h = q(jax.nn.relu(q(q(_mm(x, p["w1"])) + p["b1"])))
    return q(q(_mm(h, p["w2"])) + p["b2"])


def entropy_scores(pp, tokens, *, n_layers: int, n_heads: int,
                   n_kv_heads: int, d_head: int, q=_exact,
                   dtype=jnp.float32):
    """(B, S) int tokens -> (B,) entropy scores of proxy `pp`."""
    pp = jax.tree.map(lambda a: q(a.astype(dtype)), pp)
    d = pp["embed"].shape[1]
    x = q(jnp.take(pp["embed"], tokens, axis=0)
          * jnp.asarray(d ** 0.5, dtype))
    b, s, _ = x.shape
    w, dh = n_heads, d_head
    wk = min(w, n_kv_heads)
    g = w // wk
    att = pp["attn"]
    for li in range(n_layers):
        mu = q(jnp.mean(x, axis=-1, keepdims=True))
        xc = x - mu
        var = q(jnp.mean(q(xc * xc), axis=-1, keepdims=True))
        inv = _mlp(pp["mlp_ln"][li], var.reshape(b * s, 1), q)
        h = q(xc * inv.reshape(b, s, 1))
        h = q(h * pp["ln_scale"][li]) + pp["ln_bias"][li]
        h2 = h.reshape(b * s, d)
        qm = q(_mm(h2, att["wq"][li]))
        km = q(_mm(h2, att["wk"][li]))
        vm = q(_mm(h2, att["wv"][li]))
        if "bq" in att:
            qm = qm + att["bq"][li]
            km = km + att["bk"][li]
            vm = vm + att["bv"][li]
        qh = qm.reshape(b, s, wk, g, dh).transpose(0, 2, 3, 1, 4)
        kh = km.reshape(b, s, wk, dh).transpose(0, 2, 3, 1)
        sc = q(q(jnp.einsum("bkgsd,bkdt->bkgst", qh, kh, precision=_HI))
               * jnp.asarray(dh ** -0.5, dtype))
        probs = _mlp(pp["mlp_sm"][li], sc.reshape(-1, s), q)
        vh = vm.reshape(b, s, wk, dh).transpose(0, 2, 1, 3)
        o = q(jnp.einsum("bkgst,bktd->bkgsd", probs.reshape(b, wk, g, s, s),
                         vh, precision=_HI))
        o2 = o.transpose(0, 3, 1, 2, 4).reshape(b * s, w * dh)
        x = x + q(_mm(o2, att["wo"][li])).reshape(b, s, d)
    pooled = q(jnp.mean(x, axis=1))
    logits = q(_mm(pooled, pp["cls_head"]))
    return _mlp(pp["mlp_se"], logits, q)[:, 0].astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(n_layers, n_heads, n_kv_heads, d_head, precision):
    q = QUANT.get(precision, _exact)
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    return jax.jit(functools.partial(
        entropy_scores, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_head=d_head, q=q, dtype=dtype))


def score(pp, tokens, *, n_layers: int, n_heads: int, n_kv_heads: int,
          d_head: int, precision: str = "float32",
          block: int = 64) -> np.ndarray:
    """Entropy scores of every row of `tokens`, `block` rows at a time
    (the weights are arguments, so one program serves every proxy of a
    geometry). `precision`: "float32" (the reference), "fx16" (the
    control) or "bfloat16"."""
    fn = _jitted(n_layers, n_heads, n_kv_heads, d_head, precision)
    tokens = np.asarray(tokens)
    n = tokens.shape[0]
    pad = -n % block
    if pad:
        tokens = np.concatenate([tokens, tokens[:pad]])
    out = [np.asarray(fn(pp, jnp.asarray(tokens[i:i + block])))
           for i in range(0, len(tokens), block)]
    return np.concatenate(out)[:n].astype(np.float64)


def decode(share_rows: np.ndarray, value_rows: int,
           frac_bits: int) -> np.ndarray:
    """Reconstruct int32 ring shares (sum of the first `value_rows`
    components, mod 2**32, signed) and decode the fixed point."""
    v = np.zeros(share_rows.shape[1:], np.int64)
    for r in np.asarray(share_rows)[:value_rows]:
        v = v + r.astype(np.int64)
    v = (v + 2 ** 31) % 2 ** 32 - 2 ** 31
    return v.astype(np.float64) / 2.0 ** frac_bits
