"""Device-mesh wave execution (parallel/sharding.py + the executor).

Three layers of coverage:
  1. RULES — ShardRules.resolve / fit_spec semantics: the mpc_pod_axis
     policy (pod reserved for parties, batch stays off it), uneven dims
     dropped rather than erroring, claimed-axis reuse dropped. Runs on
     any device count (axis *presence* drives resolve; a 1x1 mesh is
     enough).
  2. KERNEL PATH — kernels/ops.secure_matmul pad-to-tile: non-tileable
     shapes are zero-padded onto the Pallas kernel (interpret mode on
     CPU) bitwise-identically to the jnp reference; pad=False falls
     back to ref, counted (and logged once) instead of silently.
     Plus the shared cached_probe memo (engine/trace.py).
  3. MESH (marked `mesh`, needs 8 forced host devices) — fit_spec on a
     real pod x data mesh, party_wave_rules geometry, and the
     end-to-end contract: `_score_phase` under mesh="host" (party ->
     pod, wave -> data NamedSharding) and mesh="shardmap" (lanes split
     across the data axis) yields entropy scores BITWISE identical to
     the single-device run, ledger_agrees holds, and the fused RING32
     combines run through the secure_matmul kernel.

CI runs the mesh layer under
XLA_FLAGS=--xla_force_host_platform_device_count=8 (smoke-mesh job).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.engine import cached_probe, cached_probe_info
from repro.kernels import ops as kops
from repro.mpc.ring import RING32, RING64
from repro.parallel import sharding

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 devices — run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")


def _mesh_1x1():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("pod", "data"))


class TestShardRules:
    def test_pod_axis_reserved_for_parties(self):
        r = sharding.ShardRules(_mesh_1x1(), mpc_pod_axis=True)
        assert r.resolve("pod") == "pod"
        assert r.resolve("wave") == "data"
        # batch must NOT claim the pod axis when it belongs to parties
        assert r.batch_axes == ("data",)
        assert r.resolve("batch") == "data"

    def test_pod_axis_joins_batch_without_mpc(self):
        r = sharding.ShardRules(_mesh_1x1(), mpc_pod_axis=False)
        assert r.resolve("batch") == ("pod", "data")

    def test_resolve_missing_axes(self):
        m = Mesh(np.array(jax.devices()[:1]), ("data",))
        r = sharding.ShardRules(m, mpc_pod_axis=True)
        assert r.resolve("pod") is None
        assert r.resolve("wave") == "data"
        assert r.resolve(None) is None

    def test_fit_spec_drops_reused_axis(self):
        # wave and batch both resolve to "data": the second claim must
        # yield (never a double-sharded spec), even at axis size 1
        r = sharding.ShardRules(_mesh_1x1(), mpc_pod_axis=True)
        spec = sharding.fit_spec(r, (4, 4), ("wave", "batch"))
        assert spec == P("data", None)

    @needs_mesh
    def test_fit_spec_uneven_dims_dropped(self):
        # a real pod(2) x data(4) mesh: dims that don't divide the axis
        # are dropped per-dim, the others still shard
        rules = sharding.party_wave_rules(2)
        assert rules.mesh.shape == {"pod": 2, "data": 4}
        spec = sharding.fit_spec(rules, (3, 5), ("pod", "wave"))
        assert spec == P(None, None)          # 3 % 2 != 0, 5 % 4 != 0
        spec = sharding.fit_spec(rules, (2, 8), ("pod", "wave"))
        assert spec == P("pod", "data")
        spec = sharding.fit_spec(rules, (2, 5, 8), ("pod", "wave", "batch"))
        # wave can't take data (5 % 4), so batch (-> data) still can
        assert spec == P("pod", None, "data")

    @needs_mesh
    def test_party_wave_rules_geometry(self):
        r2 = sharding.party_wave_rules(2)
        assert r2.mpc_pod_axis and r2.mesh.shape == {"pod": 2, "data": 4}
        # 8 devices don't split 3 ways: pod collapses, parties replicate
        r3 = sharding.party_wave_rules(3)
        assert "pod" not in r3.mesh.axis_names
        assert r3.mesh.shape == {"data": 8}
        # max_data clamps the data axis to a divisor of the lane count
        r = sharding.party_wave_rules(1, max_data=4)
        assert sharding.data_axis_size(r) == 4
        r = sharding.party_wave_rules(1, max_data=6)
        assert sharding.data_axis_size(r) in (1, 2, 3, 6)
        assert 6 % sharding.data_axis_size(r) == 0

    def test_shard_and_place_noop_without_rules(self):
        x = jnp.ones((4, 4))
        assert sharding.shard(x, "wave", None) is x
        assert sharding.place(x, "wave", None) is x


class TestSecureMatmulPad:
    def _rand(self, rng, *shape):
        return jnp.asarray(rng.integers(-2**20, 2**20, shape,
                                        dtype=np.int32))

    def _case(self, m, k, n, seed=0):
        rng = np.random.default_rng(seed)
        eps = self._rand(rng, m, k)
        dlt = self._rand(rng, k, n)
        a = self._rand(rng, 2, m, k)
        b = self._rand(rng, 2, k, n)
        c = self._rand(rng, 2, m, n)
        return eps, dlt, a, b, c

    def test_pad_to_tile_bitwise(self):
        # M=136 is NOT tileable at block 128 (136 % 128 != 0): the pad
        # path must zero-extend to 256, run the kernel, slice back —
        # exact wrapping int32 ring arithmetic, bitwise vs the reference
        args = self._case(136, 32, 64)
        before = kops.smm_stats()
        z_k = kops.secure_matmul(*args, impl="interpret")
        after = kops.smm_stats()
        z_r = kops.secure_matmul(*args, impl="ref")
        assert z_k.shape == (2, 136, 64)
        assert np.array_equal(np.asarray(z_k), np.asarray(z_r))
        assert after["kernel"] == before["kernel"] + 1
        assert after["padded"] == before["padded"] + 1

    def test_tileable_shape_skips_padding(self):
        args = self._case(64, 32, 64)
        before = kops.smm_stats()
        z_k = kops.secure_matmul(*args, impl="interpret")
        z_r = kops.secure_matmul(*args, impl="ref")
        after = kops.smm_stats()
        assert np.array_equal(np.asarray(z_k), np.asarray(z_r))
        assert after["kernel"] == before["kernel"] + 1
        assert after["padded"] == before["padded"]

    def test_pad_false_falls_back_counted(self):
        args = self._case(136, 32, 64, seed=1)
        before = kops.smm_stats()
        z = kops.secure_matmul(*args, impl="interpret", pad=False)
        after = kops.smm_stats()
        z_r = kops.secure_matmul(*args, impl="ref")
        assert np.array_equal(np.asarray(z), np.asarray(z_r))
        # the silent-cap drop is visible: counted as ref, warned once
        # (the explicit impl="ref" call lands after the snapshot)
        assert after["ref"] == before["ref"] + 1
        assert after["kernel"] == before["kernel"]
        assert kops._fallback_warned


class TestCachedProbe:
    def _geom(self):
        from repro.configs.base import ArchConfig
        from repro.core.proxy import ProxySpec
        cfg = ArchConfig(name="probe-cache-test", family="dense",
                         n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                         d_head=16, d_ff=64, vocab_size=64)
        return cfg, ProxySpec(1, 2, 4)

    def test_repeat_probe_hits_cache(self):
        cfg, spec = self._geom()
        kw = dict(batch=4, seq=8, classes=2, ring=RING64,
                  protocol="2pc", fused=True)
        led1 = cached_probe(cfg, spec, **kw)
        h0 = cached_probe_info().hits
        led2 = cached_probe(cfg, spec, **kw)
        assert cached_probe_info().hits == h0 + 1
        assert len(led1.records) == len(led2.records)
        assert led1.rounds == led2.rounds and led1.nbytes == led2.nbytes

    def test_cache_isolated_from_caller_mutation(self):
        cfg, spec = self._geom()
        kw = dict(batch=4, seq=8, classes=2, ring=RING32,
                  protocol="2pc", fused=False)
        led1 = cached_probe(cfg, spec, **kw)
        n = len(led1.records)
        led1.records.append(led1.records[0])    # caller-side mutation
        led2 = cached_probe(cfg, spec, **kw)
        assert len(led2.records) == n

    def test_distinct_geometries_miss(self):
        cfg, spec = self._geom()
        m0 = cached_probe_info().misses
        cached_probe(cfg, spec, batch=2, seq=8, classes=2, ring=RING64,
                     protocol="2pc", fused=False)
        cached_probe(cfg, spec, batch=2, seq=8, classes=2, ring=RING64,
                     protocol="3pc", fused=False)
        assert cached_probe_info().misses == m0 + 2


@needs_mesh
@pytest.mark.mesh
class TestMeshExecution:
    """End-to-end: _score_phase on 8 forced host devices must be
    bitwise identical to the single-device run, with agreeing ledgers
    and the fused RING32 combine on the kernel path."""

    def _setup(self):
        from benchmarks.common import tiny_exec_setup
        seq, classes, pool_n = 8, 2, 32
        cfg, spec, pp = tiny_exec_setup(0, seq=seq, n_classes=classes)
        pool = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (pool_n, seq))
        return cfg, spec, pp, pool

    def _run(self, cfg, spec, pp, pool, **cfg_kw):
        from repro.core.executor import ExecConfig, WaveExecutor
        ex = WaveExecutor(ExecConfig(wave=4, batch=4, ring=RING32,
                                     **cfg_kw))
        ent = ex.score_phase(jax.random.key(7), pp, cfg, pool, spec)
        return np.asarray(ent.sh), ex.reports[-1]

    def test_host_and_shardmap_bitwise_vs_single_device(self):
        cfg, spec, pp, pool = self._setup()
        ref, rep0 = self._run(cfg, spec, pp, pool)
        assert rep0.agrees()
        for mode in ("host", "shardmap"):
            got, rep = self._run(cfg, spec, pp, pool, mesh=mode,
                                 combine="interpret")
            dev = rep.device
            assert np.array_equal(ref, got), \
                f"mesh={mode} changed entropy scores"
            assert rep.agrees(), f"mesh={mode} broke ledger agreement"
            assert dev.placement == mode
            assert dev.waves_wall_s > 0.0
            assert dev.combine_kernel > 0, \
                f"mesh={mode}: combines never hit the kernel"
            assert dev.combine_ref == 0
            if mode == "host":
                assert dev.mesh_axes == {"pod": 2, "data": 4}
                assert all(w.devices_used == 8 for w in dev.waves)
            else:
                assert all(w.devices_used == 4 for w in dev.waves)

    def test_host_mesh_3pc_party_axis_collapses(self):
        # 8 devices % 3 parties != 0: pod collapses to 1, the wave axis
        # still shards, and scores stay bitwise identical
        cfg, spec, pp, pool = self._setup()
        ref, _ = self._run(cfg, spec, pp, pool, protocol="3pc")
        got, rep = self._run(cfg, spec, pp, pool, protocol="3pc",
                             mesh="host")
        assert np.array_equal(ref, got)
        assert rep.agrees()
        assert "pod" not in rep.device.mesh_axes

    def test_shardmap_rejects_wire(self):
        from repro.core.executor import ExecConfig, WaveExecutor
        with pytest.raises(ValueError, match="shardmap"):
            WaveExecutor(ExecConfig(mesh="shardmap", wire="local"))
