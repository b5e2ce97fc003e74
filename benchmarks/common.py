"""Shared benchmark plumbing: timing + `name,us_per_call,derived` CSV,
plus the tiny executor-calibration harness fig7/table4 both drive."""
from __future__ import annotations

import time


def emit(name: str, us_per_call: float, derived: dict) -> None:
    d = ";".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us_per_call:.1f},{d}", flush=True)


class timed:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        pass

    @property
    def us(self) -> float:
        return (time.time() - self.t0) * 1e6


# ---------------------------------------------------------------------------
# tiny executor-calibration harness (shared by fig7 / table4)
# ---------------------------------------------------------------------------

def tiny_exec_setup(seed: int, *, seq: int = 8, n_classes: int = 2):
    """CPU-scale (cfg, spec, pp) for driving the wave executor — the
    schedule, not the model size, is what these benchmarks exercise."""
    import dataclasses

    import jax

    from repro.configs.paper_targets import TINY_TARGET
    from repro.core import proxy as proxy_mod
    from repro.core.proxy import ProxySpec

    cfg = dataclasses.replace(TINY_TARGET, vocab_size=64, n_layers=1,
                              d_model=32, n_heads=2, n_kv_heads=2,
                              d_head=16, d_ff=64)
    spec = ProxySpec(1, 2, 4)
    pp = proxy_mod.random_proxy(jax.random.key(seed), cfg, spec,
                                seq_len=seq, n_classes=n_classes)
    return cfg, spec, pp
