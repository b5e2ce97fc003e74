"""Named host spans on the profiler's clock.

`span(name, **meta)` is `jax.profiler.TraceAnnotation("sf." + name,
**meta)`: with a `jax.profiler` trace running it writes one host event
into the same xplane as the device's operations, on the same clock, so
a trace reader can put every device op and every idle gap inside the
innermost program span around it. `meta` lands as the event's stats
(`run`, `wave`, `layer`); the event's name stays `sf.<name>`.

The profiler is the only store: this module keeps no buffer, flag or
environment variable. A span with metadata costs about 1.3 us of host
time on a TPU v5e host with no profiler running, and 1.7 us with one.
Nesting on the calling thread gives each span its parent.

Names, from the executor down (PERF.md lists the metric each serves):

  phase.setup, phase.finish          core/executor.PhaseRun (meta run)
  wave.input, wave.forward,          PhaseRun.dispatch / drain
  wave.wait                          (meta run, wave)
  stage.<ln_stats|ln_inv|ln_affine|  engine/forward.py (meta layer
  qkv|scores|softmax|av|wo|head|     inside a block)
  entropy>
  mpc.dealer                         the dealer's triples and
                                     truncation pairs (2pc, spdz2pc)
  mpc.open, mpc.combine, mpc.trunc   the Beaver opening, the post-open
                                     combine (2pc, spdz2pc), the
                                     forced truncation (every backend;
                                     aby3trunc's opens its masked value;
                                     meta site="presum" where
                                     mpc/ops.mean forces a sum's input)
  mpc.reshare                        the 3-party multiply: local cross
                                     terms and the resharing flight
                                     (3pc, aby3trunc)
  mpc.cmp, mpc.share                 secure comparison, input sharing
  mpc.key                            MPCEngine's per-op key derivation

A span inside a function that runs under `jax.jit` or `jax.eval_shape`
fires when the function is traced, not when the compiled program runs:
once per trace on the `mesh="shardmap"` path and in the `TraceEngine`
cost probe. That is harmless, and such spans carry no device time.
"""
import jax


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A context manager that records `sf.<name>` in a running trace;
    meta given as None is left out."""
    if None in meta.values():
        meta = {k: v for k, v in meta.items() if v is not None}
    return jax.profiler.TraceAnnotation("sf." + name, **meta)
