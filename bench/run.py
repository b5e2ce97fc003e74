"""One run of one benchmark cell on the chip.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and its files by name, makes weights
and pools from --seed, warms up the cell's shapes (set-up), drives the
cell's traffic for --seconds and then to the end of the unit of work in
flight (the window), and compares every answer of the window with the
plain reference (bench/reference.py). With --trace 0 the last line of
stdout holds the end-to-end metrics; with --trace 1 the profiler traces
the window and the line holds the per-layer metrics. Exits non-zero,
with no result line, where JAX finds no TPU or fewer chips than the
cell asks for.

  --rehearse   the same run on the CPU at the tiny sizes of
               bench/rehearsal.json with interpret-mode kernels; prints
               no result line
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _peaks(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, log=print) -> dict:
    """Set up, measure and check one cell; returns the result object
    (without the device's identity, which the caller adds)."""
    from bench import harness

    cell = harness.load_cell(workload, rehearse=rehearse)
    harness.place_compile_cache()
    counter = harness.CompileCounter()
    spans = harness.Spans()
    from repro.core.executor import PhaseRun
    spans.wrap(PhaseRun, "dispatch", "bench.dispatch")
    spans.wrap(PhaseRun, "drain", "bench.drain")
    spans.wrap(PhaseRun, "finish", "bench.finish")

    try:
        return _measure(cell, seed, seconds, trace, rehearse, counter, spans,
                        log)
    finally:
        spans.unwrap()


def _measure(cell, seed, seconds, trace, rehearse, counter, spans, log):
    import jax
    from bench import harness, trace_reduce
    from bench.driver import Score

    drv = Score(cell, seed, "interpret" if rehearse else "auto")
    drv.setup()

    if trace:
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(harness.TRACE_DIR),
                                 profiler_options=opts)
    spans.total.clear()
    c0 = counter.n
    setup_s = time.time() - T_START
    t0 = time.perf_counter()
    error = None
    with spans.span(trace_reduce.WINDOW_SPAN):
        u = 0
        while True:
            try:
                with spans.span("bench.unit"):
                    drv.unit(u)
            except Exception:  # noqa: BLE001 - the run reports it as failed
                error = traceback.format_exc()
                drv.failed += 1
                break
            u += 1
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    compiles = counter.n - c0
    if trace:
        jax.profiler.stop_trace()
    span_totals = dict(spans.total)
    devs = jax.local_devices()
    stats = [d.memory_stats() or {} for d in devs[:cell.chips]]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    drv.release()
    checks = drv.check()
    if error:
        log(error, file=sys.stderr)

    tr = None
    if trace:
        path = trace_reduce.latest_xplane(str(harness.TRACE_DIR))
        if path:
            events = trace_reduce.load(path)
            log(f"trace {Path(path).stat().st_size} bytes, device lines "
                f"{events['lines']}, {len(events['spans'])} host spans",
                file=sys.stderr)
            tr = trace_reduce.reduce(events)
        shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)

    result = {
        "correct": error is None and drv.failed == 0
        and all(c.ok for c in checks),
        "attempted": drv.attempted, "failed": drv.failed, "metrics": {},
        "device": {"memory_peak_bytes": int(mem_peak)},
    }
    if not trace:
        for m in cell.end_to_end:
            value = {"candidates_per_s": drv.candidates / window_s,
                     "setup_s": setup_s}[m["name"]]
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    else:
        kind = devs[0].device_kind
        rec = harness.Record(
            cell=cell, window_s=window_s, phases=drv.window_phases, compiles=compiles, trace=tr,
            peak=None if rehearse else _peaks(kind))
        for m in cell.per_layer:
            v = harness.read_metric(m["name"], rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
        if tr is not None:
            result["device"].update(busy_s=tr["busy_s"],
                                    window_s=tr["window_s"])
            result["breakdown"] = {"device_ops": tr["device_ops"],
                                   "idle_gaps": tr["idle_gaps"]}
    log(f"window {window_s:.3f}s, {drv.attempted} units, "
        f"{drv.candidates} candidates, {compiles} compiles in window, "
        f"set-up {setup_s:.3f}s; spans "
        + json.dumps({k: round(v, 4) for k, v in span_totals.items()}),
        file=sys.stderr)
    for c in checks:
        log(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    try:
        chips = harness.load_cell(args.workload).chips
    except harness.CellError as e:
        print(e, file=sys.stderr)
        return 2
    devs = jax.devices()
    d0 = devs[0]
    print(f"platform {d0.platform}, device_kind {d0.device_kind}, "
          f"count {len(devs)}", file=sys.stderr)
    if args.rehearse:
        if d0.platform != "cpu":
            print("--rehearse runs on the CPU only", file=sys.stderr)
            return 2
    else:
        if d0.platform != "tpu":
            print(f"no TPU found (platform {d0.platform})", file=sys.stderr)
            return 1
        _peaks(d0.device_kind)
    if len(devs) < chips:
        print(f"{args.workload} needs {chips} chips, found {len(devs)}",
              file=sys.stderr)
        return 1
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearse=args.rehearse)
    if args.rehearse:
        print(f"rehearsal correct={result['correct']}; not a chip result",
              file=sys.stderr)
        return 0 if result["correct"] else 1
    checks = result.pop("checks")
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devs), **result["device"]}
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
