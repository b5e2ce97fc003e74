"""TraceEngine — the zero-FLOP cost probe.

Runs the MPC op stream of the unified forward under `jax.eval_shape`:
the Python protocol executes (so every `comm.record` fires with real
static shapes) but no array math does.  This is the probe the wave
executor used to improvise inline; it also prices *paper-scale*
geometries without materializing a single weight — `abstract_shares`
builds a ShapeDtypeStruct proxy pytree, so a BERT-scale per-batch
Ledger costs microseconds (benchmarks/table3_baselines.py). Both the
ring and the protocol backend are probe parameters: the same abstract
run prices the 2PC dealer stream (offline channel included) or the
3PC resharing stream.
"""
import contextlib
import functools

import jax

from repro.engine.forward import proxy_entropy
from repro.engine.mpc import MPCEngine
from repro.mpc import comm, fusion, protocols
from repro.mpc.comm import Ledger
from repro.mpc.ring import RING64, RingSpec, x64_scope
from repro.mpc.sharing import Share


class TraceEngine:
    """Probe engine: prices the MPC op stream via `probe()`. It does
    not execute forwards itself — attempting to use it as a tensor
    engine fails loudly rather than pretending to hold data."""

    kind = "trace"

    def __init__(self, ring: RingSpec = RING64, variant=None,
                 protocol: str = "2pc"):
        self.ring = ring
        self.variant = variant
        self.protocol = protocol

    def fused(self, label):
        """No-op: the probe prices through MPCEngine, which batches for
        itself; TraceEngine used directly has no wire to compress."""
        return contextlib.nullcontext()

    def probe(self, pp_sh, cfg, spec, batch_shape, key=None,
              variant=None, fused: bool = False) -> Ledger:
        """Ledger of ONE batch (B, S, d) of the share-level forward.

        `pp_sh` may hold real share arrays or ShapeDtypeStructs — both
        flow through eval_shape untouched.  `fused=True` probes the
        round-compressed stream (the op trace runs under
        `fusion.flight_scope`, exactly as the executor runs it). The
        same stream comes back inside a `comm.wave_scope` or under a
        `WireTape`, and nothing lands on the tape.
        """
        ring = self.ring
        proto = self.protocol
        n_parties = protocols.get(proto).n_parties
        variant = self.variant if variant is None else variant
        key = jax.random.key(0) if key is None else key

        def fwd(pp, sh, k):
            eng = MPCEngine(ring=ring, variant=variant,
                            protocol=proto).with_key(k)
            with fusion.flight_scope(enabled=fused):
                return proxy_entropy(eng, pp, cfg, Share(sh, ring, proto),
                                     spec, variant).sh

        ctx = x64_scope() if ring.bits >= 64 else contextlib.nullcontext()
        # hermetic: an ambient wave scope must not scale the per-batch
        # records, and a probe is no execution to capture on a WireTape
        with ctx, comm.ledger_scope() as led, comm.wave_scope(1), \
                comm.wire_tape_scope(None):
            jax.eval_shape(fwd, pp_sh,
                           jax.ShapeDtypeStruct(
                               (n_parties,) + tuple(batch_shape),
                               ring.dtype), key)
        return led

    def embed(self, pp, x_in, cfg):
        raise TypeError(
            "TraceEngine measures cost streams abstractly — call "
            "TraceEngine.probe(pp_sh, cfg, spec, batch_shape) instead of "
            "running a forward through it; use ClearEngine/MPCEngine to "
            "execute")


@functools.lru_cache(maxsize=256)
def _cached_probe(cfg, spec, seq: int, classes: int, batch: int,
                  ring: RingSpec, protocol: str, fused: bool,
                  variant) -> Ledger:
    pp_sh = abstract_shares(cfg, spec, seq, classes, ring, protocol)
    return TraceEngine(ring, variant, protocol=protocol).probe(
        pp_sh, cfg, spec, (batch, seq, cfg.d_model), fused=fused)


def cached_probe(cfg, spec, *, batch: int, seq: int, classes: int,
                 ring: RingSpec, protocol: str = "2pc",
                 fused: bool = False, variant=None) -> Ledger:
    """Per-batch probe ledger, memoized on the full probe geometry
    (arch, proxy, batch/seq/classes, ring, protocol, fused, variant).

    A probe costs ~1 s of abstract tracing and the same geometry is
    re-probed per profile sweep / per executed phase — this cache turns
    repeats into microseconds. `ArchConfig`/`ProxySpec`/`RingSpec` are
    frozen (hashable) and the probe key is irrelevant under eval_shape,
    so the memo is sound. Returns a fresh shallow copy so callers may
    extend/mutate their ledger without corrupting the cache."""
    led = _cached_probe(cfg, spec, seq, classes, batch, ring, protocol,
                        fused, variant)
    out = Ledger()
    out.records.extend(led.records)
    return out


def cached_probe_info():
    """lru cache stats for the shared probe memo (hits/misses)."""
    return _cached_probe.cache_info()


def abstract_shares(cfg, spec, seq_len: int, n_classes: int,
                    ring: RingSpec = RING64, protocol: str = "2pc"):
    """ShapeDtypeStruct pytree shaped like `proxy.share_proxy`'s output
    (minus the embedding table, which the MPC forward never touches) —
    lets `TraceEngine.probe` price paper-scale proxies for free. The
    leading party-axis size comes from the protocol backend."""
    dh, w = cfg.d_head, spec.n_heads
    wk = min(w, cfg.n_kv_heads)
    L, hid = spec.n_layers, spec.mlp_dim
    p = protocols.get(protocol).n_parties

    def sh(*shape):
        return Share(jax.ShapeDtypeStruct((p,) + shape, ring.dtype), ring,
                     protocol)

    def mlp(d_in, d_out):
        return {"w1": sh(d_in, hid), "b1": sh(hid),
                "w2": sh(hid, d_out), "b2": sh(d_out)}

    return {
        "cls_head": sh(cfg.d_model, n_classes),
        "attn": {
            "wq": sh(L, cfg.d_model, w * dh),
            "wk": sh(L, cfg.d_model, wk * dh),
            "wv": sh(L, cfg.d_model, wk * dh),
            "wo": sh(L, w * dh, cfg.d_model),
        },
        "ln_scale": sh(L, cfg.d_model),
        "ln_bias": sh(L, cfg.d_model),
        "mlp_sm": [mlp(seq_len, seq_len) for _ in range(L)],
        "mlp_ln": [mlp(1, 1) for _ in range(L)],
        "mlp_se": mlp(n_classes, 1),
    }
