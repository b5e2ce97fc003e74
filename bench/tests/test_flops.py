"""Operation and byte counts against hand counts at one small shape."""
import jax
import numpy as np
import pytest

from bench import flops

DIMS = dict(seq=4, d_model=8, d_head=2, n_layers=1, n_heads=2, n_kv_heads=2)


def test_proxy_forward_ops_hand_count():
    # per block: q 2*4*8*4 = 256; k, v 512; scores + probs@v
    # 2*2*2*4*4*2 = 256; o 256; softmax emulator 2*2*4*(4*3 + 3*4) = 384;
    # rsqrt emulator 2*4*(3 + 3) = 48. Head: 2*8*2 + 2*(2*3 + 3) = 50.
    got = flops.proxy_forward_ops(mlp_dim=3, n_classes=2, **DIMS)
    assert got == 256 + 512 + 256 + 256 + 384 + 48 + 50


def test_combine_counts_hand_count():
    assert flops.combine_shapes(batch=2, n_classes=2, **DIMS) == [
        (8, 8, 4), (8, 8, 4), (8, 8, 4), (8, 4, 8), (2, 8, 2)]
    # 2 parties x (eps@b + a@delta) x 2 ops per multiply-add x 2*3*4
    assert flops.combine_ops(2, 3, 4) == 2 * 2 * 2 * 24
    # eps 6 + delta 12 + a 12 + b 24 + c 16 + z 16 int32 words
    assert flops.combine_bytes(2, 3, 4) == 4 * 86
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 1000.0}
    assert flops.ideal_seconds(200, 1000, peak) == 2.0
    assert flops.ideal_seconds(50, 3000, peak) == 3.0


@pytest.mark.parametrize("n_layers,n_heads", [(1, 1), (2, 4)])
def test_combine_shapes_match_the_program_kernel_calls(n_layers, n_heads):
    """The shapes list holds exactly as many combines as a 2PC RING32
    phase hands the kernel (the program's own dispatch counter)."""
    from repro.configs.base import ArchConfig
    from repro.core.executor import ExecConfig, WaveExecutor
    from repro.core.proxy import ProxySpec, random_proxy
    from repro.mpc.ring import RING32
    arch = ArchConfig(name="t", family="dense", n_layers=2, d_model=16,
                      n_heads=4, n_kv_heads=4, d_head=4, d_ff=32,
                      vocab_size=64, norm_type="layernorm", act="gelu")
    spec = ProxySpec(n_layers, n_heads, 4)
    pp = random_proxy(jax.random.key(0), arch, spec, 8, 2)
    tok = np.random.default_rng(0).integers(0, 64, (8, 8)).astype(np.int32)
    ex = WaveExecutor(ExecConfig(ring=RING32, wave=2, batch=2,
                                 combine="interpret"))
    ex.score_phase(jax.random.key(1), pp, arch, tok, spec)
    rep = ex.reports[-1]
    shapes = flops.combine_shapes(batch=2, seq=8, d_model=16, d_head=4,
                                  n_layers=n_layers, n_heads=n_heads,
                                  n_kv_heads=4, n_classes=2)
    assert rep.device.combine_kernel == rep.n_waves * len(shapes)
