"""Backend compiles inside the measured window, counted by the JAX
runtime (jax.monitoring's backend_compile_duration event)."""


def read(rec):
    return float(rec.compiles)
