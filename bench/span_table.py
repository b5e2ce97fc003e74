"""One traced run of a cell, read down to the program's own spans.

  python3 bench/span_table.py --workload <name> --seed <n> --seconds <s>
                              [--rehearse]

Runs the cell exactly as `bench/run.py --trace 1` does (bench/run.py's
run_cell), keeps the `sf.*` host spans of the same trace
(bench/span_reduce.py), and prints on stderr one row per span name
(count, inclusive and self ms per wave), then on stdout one JSON line:
the run's result, the per-wave split of the forward (wave_input_ms,
wave_wait_ms, dealer_ms, online_ms, local_ms, forward_ms), every idle
gap's seconds by innermost span of either family (`bench.*` or `sf.*`,
by trace_reduce.reduce) and the share of them under `sf.*`,
the spans per wave, and the host cost of one span with the profiler off
and on. Exits non-zero where JAX finds no TPU (with --rehearse: where
it is not on the CPU, at the tiny sizes of bench/rehearsal.json).
"""
import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def span_cost_us(n: int = 20000) -> dict:
    """Host microseconds per span with metadata (enter and exit), with
    no profiler running and with one."""
    import jax
    from repro.spans import span

    def per_span():
        t0 = time.perf_counter()
        for i in range(n):
            with span("cost", run=0, wave=i):
                pass
        return 1e6 * (time.perf_counter() - t0) / n

    off = per_span()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            on = per_span()
        finally:
            jax.profiler.stop_trace()
    return {"off": off, "on": on}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from bench import harness, run, span_reduce, trace_reduce
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"platform {platform}: a chip run needs the TPU, "
              "--rehearse the CPU", file=sys.stderr)
        return 1

    seen = {}
    load = trace_reduce.load

    def load_both(path):
        seen["events"] = load(path)
        seen["spans"] = span_reduce.load(path)
        return seen["events"]

    trace_reduce.load = load_both
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True,
                              rehearse=args.rehearse)
    finally:
        trace_reduce.load = load
    if "events" not in seen:
        print("the run left no trace", file=sys.stderr)
        return 1

    events, spans = seen["events"], seen["spans"]
    win = [s for s in events["spans"] if s[2] == trace_reduce.WINDOW_SPAN]
    w0, w1 = win[0][0], win[0][0] + win[0][1]
    per_wave = span_reduce.wave_metrics(spans, w0, w1)
    n_waves = sum(1 for s in spans if s[2] == span_reduce.FORWARD
                  and w0 <= s[0] <= w1)
    table = span_reduce.host_spans(spans, w0, w1)
    print(f"{'span':24s} {'n/wave':>9s} {'incl ms/wave':>13s} "
          f"{'self ms/wave':>13s}", file=sys.stderr)
    for name, r in sorted(table.items(), key=lambda kv: -kv[1]["incl_s"]):
        print(f"{name:24s} {r['count'] / max(n_waves, 1):9.2f} "
              f"{1000 * r['incl_s'] / max(n_waves, 1):13.3f} "
              f"{1000 * r['self_s'] / max(n_waves, 1):13.3f}",
              file=sys.stderr)
    t0 = time.perf_counter()
    reduced = trace_reduce.reduce(dict(events, spans=events["spans"] + spans),
                                  top=10 ** 6) or {"idle_gaps": []}
    print(f"idle gaps attributed in {time.perf_counter() - t0:.2f} s",
          file=sys.stderr)
    idle = dict(reduced["idle_gaps"])
    idle_s = sum(idle.values())
    window_s = (w1 - w0) / 1e9
    pool = harness.load_cell(args.workload,
                             rehearse=args.rehearse).traffic["pool"]
    out = {
        "result": result,
        "per_wave": per_wave,
        "waves": n_waves,
        "spans_per_wave": sum(r["count"] for r in table.values())
        / max(n_waves, 1),
        "host_spans": table,
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        "idle_s": idle_s,
        "idle_share_sf": (sum(v for k, v in idle.items()
                              if k.startswith(span_reduce.PREFIX)) / idle_s
                          if idle_s else None),
        "traced_candidates_per_s": (result["attempted"] - result["failed"])
        * pool / window_s,
        "span_cost_us": span_cost_us(),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
