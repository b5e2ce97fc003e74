"""The whole scoring step's share of the chip's peak, percent.

Operations of the float proxy forward at each scored phase's geometry
(bench/flops.py), times the candidates the window scored, over the
window and the chip's integer peak (393 TOP/s on a v5e: the peak that
bounds ring arithmetic). The plan's clear-side training is not counted.
"""
from bench import flops


def read(rec):
    if rec.peak is None:
        return None
    d = rec.dims
    ops = sum(p.candidates * flops.proxy_forward_ops(
        seq=d["seq"], d_model=d["d_model"], d_head=d["d_head"],
        n_layers=p.n_layers, n_heads=p.n_heads, n_kv_heads=d["n_kv_heads"],
        mlp_dim=p.mlp_dim, n_classes=d["n_classes"]) for p in rec.phases)
    return 100.0 * ops / rec.window_s / rec.peak["int8_ops_per_s"]
