"""Record the small trace that test_trace_reduce.py reads, on a TPU.

  python3 bench/tests/record_trace.py

One 2PC RING32 phase of a small proxy (two waves, the secure_matmul
kernel compiled for the chip), traced inside a `bench.window` span with
the benchmark's own spans around each wave, reduced to the plain event
lists of trace_reduce.load and written to data/trace_small.json.gz.
"""
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(HERE / "data" / "trace_small.json.gz"))
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        print("records a TPU trace: run it on the chip", file=sys.stderr)
        return 1
    from bench import harness, trace_reduce
    from repro.configs.base import ArchConfig
    from repro.core.executor import ExecConfig, PhaseRun
    from repro.core.proxy import ProxySpec, random_proxy
    from repro.mpc.ring import RING32

    arch = ArchConfig(name="t", family="dense", n_layers=1, d_model=128,
                      n_heads=2, n_kv_heads=2, d_head=64, d_ff=256,
                      vocab_size=256, norm_type="layernorm", act="gelu")
    spec = ProxySpec(1, 2, 4)
    pp = random_proxy(jax.random.key(0), arch, spec, 16, 2)
    tok = np.random.default_rng(0).integers(0, 256, (16, 16)).astype(
        np.int32)
    cfg = ExecConfig(ring=RING32, wave=2, batch=4)

    def phase():
        run = PhaseRun(cfg, jax.random.key(1), pp, arch, tok, spec)
        for wi in range(run.n_waves):
            with spans.span("bench.dispatch"):
                run.dispatch(wi)
        with spans.span("bench.drain"):
            run.drain()
        return run.finish()

    spans = harness.Spans()
    phase()                                    # compile outside the trace
    d = tempfile.mkdtemp()
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(d, profiler_options=opts)
        with spans.span(trace_reduce.WINDOW_SPAN):
            phase()
        jax.profiler.stop_trace()
        events = trace_reduce.load(trace_reduce.latest_xplane(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    out = Path(args.out)
    out.write_bytes(gzip.compress(json.dumps(events).encode()))
    print(json.dumps(trace_reduce.reduce(events))[:2000])
    print(f"wrote {out} ({out.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
