"""Batched serving demo: continuous-batching server over a smoke model.

    PYTHONPATH=src python examples/serve_demo.py [--arch mamba2_2_7b]

`--appraise` demos the other serving mode — the appraisal service: two
queued private-selection sessions (the second a duplicate of the first)
interleaved through repro.serve.AppraisalServer, with the duplicate's
phases served from the cross-session cache.

    PYTHONPATH=src python examples/serve_demo.py --appraise
"""
import argparse
import sys

sys.path.insert(0, "src")

import numpy as np  # noqa: E402

from repro.launch.serve import ServeConfig, Server, Request  # noqa: E402

# the SERVE/SELECT shared per-phase report shape (PhaseReport.as_dict)
PHASE_KEYS = {"n_batches", "n_waves", "protocol", "lat_rounds",
              "bw_rounds", "nbytes", "offline_nbytes", "makespan_wan_s",
              "wall_s", "waves_wall_s", "device", "wire"}


def appraise_demo() -> None:
    from repro.launch.serve import appraise

    rep = appraise(n_sessions=2, n_pool=48, out_path=None)
    t = rep["throughput"]
    print(f"[serve] 2 appraisals: {t['serve_appraisals_per_hour']:.1f}/h "
          f"served vs {t['sequential_appraisals_per_hour']:.1f}/h "
          f"sequential ({t['speedup']:.2f}x); "
          f"cache hits={rep['cache']['hits']}")
    # pinned output shape: per-phase dicts are exactly the SELECT shape,
    # the duplicate session was served from cache, ledgers reconcile
    assert len(rep["sessions"]) == 2
    for sess in rep["sessions"]:
        assert sess["ledger_agrees"] and sess["n_selected"] > 0
        for ph in sess["phases"]:
            assert set(ph) == PHASE_KEYS, sorted(set(ph) ^ PHASE_KEYS)
    assert rep["cache"]["hits"] + rep["cache"]["coalesced_waits"] > 0
    assert rep["ledger_agrees"] is True
    assert rep["dealer"]["dealer_stall_s"] == 0.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--appraise", action="store_true",
                    help="demo the appraisal service instead of token "
                         "decoding")
    args = ap.parse_args()
    if args.appraise:
        appraise_demo()
        return
    srv = Server(ServeConfig(arch=args.arch, slots=3, max_new=8))
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, srv.cfg.vocab_size,
                                    size=int(rng.integers(4, 12))))
            for i in range(args.requests)]
    out = srv.run(reqs)
    print(f"[serve] {out['requests']} requests -> {out['tokens']} tokens "
          f"@ {out['tok_per_s']:.1f} tok/s")
    assert out["requests"] == args.requests
    assert all(len(v) == 8 for v in out["outputs"].values())


if __name__ == "__main__":
    main()
