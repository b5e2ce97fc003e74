"""The secure_matmul combine program's share of its roofline, percent.

Device time: every execution of the jitted combine program (module
`jit__combine`: the digit split and the Pallas kernel) in the window's
trace. Ideal time: for every combine the window's 2PC forwards made,
the larger of its ring products at the chip's integer peak and its
int32 bytes at the HBM bandwidth (bench/flops.py), which counts the
work any implementation must do and not today's int8 digit dots. None
without a device trace or where no combine ran.
"""
import sys

from bench import flops

MODULE = "jit__combine"


def read(rec):
    tr = rec.trace
    if tr is None or rec.peak is None:
        return None
    t = tr["module_s"].get(MODULE, 0.0)
    if t <= 0:
        return None
    ideal, calls = 0.0, 0
    for p in rec.phases:
        d = rec.dims
        shapes = flops.combine_shapes(
            batch=p.batch, seq=d["seq"], d_model=d["d_model"],
            d_head=d["d_head"], n_layers=p.n_layers, n_heads=p.n_heads,
            n_kv_heads=d["n_kv_heads"], n_classes=d["n_classes"])
        ideal += p.n_batches * sum(
            flops.ideal_seconds(flops.combine_ops(*s),
                                flops.combine_bytes(*s), rec.peak)
            for s in shapes)
        calls += len(p.waves) * len(shapes)
    seen = tr["module_count"].get(MODULE, 0)
    print(f"secure_matmul_roofline: {seen} {MODULE} executions traced, "
          f"{calls} expected; ideal {ideal:.6f}s of {t:.6f}s",
          file=sys.stderr)
    return 100.0 * ideal / t
