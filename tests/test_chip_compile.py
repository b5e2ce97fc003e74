"""Compile-only checks of the main path's Pallas kernel for a TPU v5e.

The TPU compiler is installed with jax; it compiles for a chip that is
described (`v5e:2x2`), not attached, so these run on the CPU. Nothing
executes: they prove Mosaic accepts `secure_matmul` at the shapes the
BERT-base proxies hand it, plain and under `jax.vmap` over the wave
lanes as the executor calls it, and on the four-chip party mesh of the
executor's host placement. The topology is described inside a fixture, never at import, so that every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
from __future__ import annotations

import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.kernels import ops as kops
from repro.parallel import sharding

# (rows, K, N) of every fused 2PC combine of the BERT-base proxies at
# batch 8 x seq 128 (TraceEngine probes of two_phase_default(12)):
# <3,12,16> runs (1024, 768, 768) q/k/v/o projections and the (8, 768, 2)
# class head; <1,1,2> adds the single-head (1024, 768, 64) projections
# and its (1024, 64, 768) output projection
SHAPES = [(1024, 768, 768), (8, 768, 2), (1024, 768, 64), (1024, 64, 768)]
WAVE_LANES = 2


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(m, k, n, lanes):
    shp = [(m, k), (k, n), (2, m, k), (2, k, n), (2, m, n)]
    return [((lanes,) if lanes else ()) + s for s in shp]


@pytest.mark.parametrize("precision", [None, "float32"],
                         ids=["default", "f32-ambient"])
@pytest.mark.parametrize("lanes", [None, WAVE_LANES], ids=["plain", "vmap"])
@pytest.mark.parametrize("m,k,n", SHAPES, ids=lambda v: str(v))
def test_secure_matmul_compiles_for_v5e(one_chip, no_persistent_cache,
                                        m, k, n, lanes, precision):
    """Also under an ambient float32 matmul precision, which a caller
    sets for its float reference: it must not reach the int8 dots."""
    fn = lambda *a: kops.secure_matmul(*a, impl="pallas")  # noqa: E731
    if lanes:
        fn = jax.vmap(fn)
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in _shapes(m, k, n, lanes)]
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = jax.eval_shape(fn, *args)
    assert out.shape == ((lanes,) if lanes else ()) + (2, m, n)
    assert out.dtype == jnp.int32


@pytest.mark.parametrize("lanes", [None, WAVE_LANES], ids=["plain", "vmap"])
def test_secure_matmul_compiles_on_party_mesh(v5e_2x2, no_persistent_cache,
                                              lanes):
    """Under the executor's host placement (a pod x data mesh, each
    party's shares on its own pod slice, wave lanes on data) the kernel
    is called outside any shard_map body. Mosaic cannot partition a
    kernel, so the call must lower through one itself."""
    rules = sharding.party_wave_rules(2, devices=v5e_2x2.devices)
    assert dict(rules.mesh.shape) == {"pod": 2, "data": 2}
    m, k, n = SHAPES[0]
    specs = [P(), P(), P("pod"), P("pod"), P("pod")]
    fn = lambda *a: kops.secure_matmul(*a, impl="pallas")  # noqa: E731
    if lanes:
        specs = [P("data", *s) for s in specs]
        fn = jax.vmap(fn)
    args = [jax.ShapeDtypeStruct(s, jnp.int32,
                                 sharding=NamedSharding(rules.mesh, sp))
            for s, sp in zip(_shapes(m, k, n, lanes), specs)]
    with sharding.rules_scope(rules):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
