"""Operations and bytes, counted from shapes.

A multiply-add counts as two operations. Only matrix products are
counted; elementwise work is left out, as MFU conventionally does.
"""
from __future__ import annotations


def proxy_forward_ops(*, seq: int, d_model: int, d_head: int, n_layers: int,
                      n_heads: int, n_kv_heads: int, mlp_dim: int,
                      n_classes: int) -> int:
    """Operations of the float proxy forward for one candidate: per block
    the q/k/v/o projections, scores and probs @ v over the kept heads,
    and the softmax and rsqrt emulators; then the class head and the
    entropy emulator."""
    s, d, h = seq, d_model, mlp_dim
    w, wk = n_heads, min(n_heads, n_kv_heads)
    per_layer = (2 * s * d * w * d_head            # q
                 + 2 * 2 * s * d * wk * d_head     # k, v
                 + 2 * 2 * w * s * s * d_head      # scores, probs @ v
                 + 2 * s * w * d_head * d          # o
                 + 2 * w * s * (s * h + h * s)     # softmax emulator
                 + 2 * s * (h + h))                # rsqrt emulator
    head = 2 * d * n_classes + 2 * (n_classes * h + h)
    return n_layers * per_layer + head


def combine_shapes(*, batch: int, seq: int, d_model: int, d_head: int,
                   n_layers: int, n_heads: int, n_kv_heads: int,
                   n_classes: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of every Beaver combine one batch's 2PC forward hands
    the secure_matmul kernel: the matmuls with a 2-D weight on the right
    (q, k, v, o of each block, then the class head)."""
    rows = batch * seq
    w, wk = n_heads * d_head, min(n_heads, n_kv_heads) * d_head
    per_layer = [(rows, d_model, w), (rows, d_model, wk),
                 (rows, d_model, wk), (rows, w, d_model)]
    return per_layer * n_layers + [(batch, d_model, n_classes)]


def combine_ops(m: int, k: int, n: int) -> int:
    """Ring products one two-party combine needs: each party's eps @ b
    and a @ delta, M x K x N multiply-adds each."""
    return 2 * 2 * 2 * m * k * n


def combine_bytes(m: int, k: int, n: int) -> int:
    """int32 operands read (eps, delta, and both parties' a, b, c) and
    both parties' results written."""
    return 4 * (m * k + k * n + 2 * m * k + 2 * k * n + 2 * m * n
                + 2 * m * n)


def ideal_seconds(ops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: operations at the integer peak or
    bytes at the HBM bandwidth, whichever bounds."""
    return max(ops / peak["int8_ops_per_s"], nbytes / peak["hbm_bytes_per_s"])
