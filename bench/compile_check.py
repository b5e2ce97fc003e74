"""Compile-only rehearsal, for a described TPU v5e, of every secure_matmul
combine a 2PC cell hands the kernel at its own batch.

  JAX_PLATFORMS=cpu python3 bench/compile_check.py [--workload bertmini2pc.probe]

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what Mosaic would
refuse on the chip. Each shape is compiled under jax.vmap over the wave
lanes, as the executor calls it. Prints one line per shape and no
result line.
"""
import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="bertmini2pc.probe")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from bench import flops, harness
    from repro.kernels import ops as kops

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(args.workload)
    c = cell.config
    lanes = c["mpc"]["wave"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shapes = set()
    for n_layers, n_heads, _, _ in c["schedule"]:
        shapes.update(flops.combine_shapes(
            batch=c["selection"]["score_batch"], seq=c["seq_len"],
            d_model=c["hidden_size"], d_head=c["head_dim"],
            n_layers=1, n_heads=n_heads,
            n_kv_heads=c["num_attention_heads"], n_classes=c["n_classes"]))
    fn = jax.vmap(lambda *a: kops.secure_matmul(*a, impl="pallas"))
    for m, k, n in sorted(shapes):
        args_ = [jax.ShapeDtypeStruct((lanes,) + s, jnp.int32, sharding=one)
                 for s in [(m, k), (k, n), (2, m, k), (2, k, n), (2, m, n)]]
        compiled = jax.jit(fn).lower(*args_).compile()
        ok = "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        print(f"({m}, {k}, {n}) x {lanes} lanes: compiled, kernel "
              f"{'present' if ok else 'MISSING'}, temp "
              f"{getattr(mem, 'temp_size_in_bytes', 'n/a')} bytes",
              flush=True)
        if not ok:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
