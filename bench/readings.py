"""Readings for the limits of `correct`: over many seeds, in one process,
the program's gap to the plain reference and the controls' gaps.

  python3 bench/readings.py --workload <name> --seeds <n> [<n> ...]
                            [--units 1] [--rehearse]

For each seed: the cell's set-up (warm-up on the first seed only), then
`--units` units of its traffic, then for every compared number the
program's reading and that of the reference computed as the control
(bench/reference.py: "fx16", and "bfloat16" for the record), on the
same answers. One JSON line per seed on stdout, also appended to
chiprun_out/readings_<workload>.jsonl. The benchmark's own runs do not
run the controls.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def collect(workload: str, seeds, units: int = 1, rehearse: bool = False,
            out=None):
    from bench import harness
    from bench.driver import Score
    cell = harness.load_cell(workload, rehearse=rehearse)
    harness.place_compile_cache()
    rows = []
    for i, seed in enumerate(seeds):
        drv = Score(cell, seed, "interpret" if rehearse else "auto")
        drv.setup(warm=i == 0)
        for u in range(units):
            drv.unit(u)
        drv.release()
        row = {"workload": workload, "seed": seed,
               "readings": drv.readings(),
               "checks": {c.name: c.value for c in drv.check()}}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out is not None:
            with open(out, "a") as f:
                f.write(line + "\n")
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--units", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    plat = jax.devices()[0].platform
    if (plat == "tpu") == args.rehearse:
        print(f"platform {plat}: --rehearse is for the CPU, and only there",
              file=sys.stderr)
        return 1
    out = ROOT / "chiprun_out" / f"readings_{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    collect(args.workload, args.seeds, args.units, args.rehearse, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
