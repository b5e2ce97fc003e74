"""Device time of the 2PC dealer's truncation pairs, in ms a wave.

Every execution of the dealer's jitted truncation-pair program (module
`jit__trunc_pair_raw`) in the window's trace, over the window's waves.
It grows with the elements of every truncation the scale lattice
forces, the pre-sum truncations of LayerNorm's statistics among them.
None without a device trace or where no pair was drawn.
"""
MODULE = "jit__trunc_pair_raw"


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    t = tr["module_s"].get(MODULE, 0.0)
    waves = sum(len(p.waves) for p in rec.phases)
    if t <= 0 or not waves:
        return None
    return 1000.0 * t / waves
