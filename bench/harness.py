"""What every cell shares: loading a cell by name, the compile cache,
the compile counter, host spans, and the record a metric reader reads.

A cell is found by the names in BENCHMARK.json: its configuration file,
`bench/traffic/<traffic>.json`, `bench/limits/<workload>.json`, and one
reader `bench/metrics/<metric>.py` per per-layer metric.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


class CellError(RuntimeError):
    pass


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise CellError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_cell(workload: str, rehearse: bool = False) -> Cell:
    """The cell `workload` of BENCHMARK.json; with `rehearse`, at the
    tiny sizes and limits of bench/rehearsal.json."""
    bench = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    confs = {c["name"]: c["file"] for c in bench["configs"]}
    config = _load_json(ROOT / confs[w["config"]])
    traffic = _load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(BENCH / "limits" / f"{workload}.json")
    if rehearse:
        small = _load_json(BENCH / "rehearsal.json")
        config = {**config, **small["config"]}
        traffic = {**traffic, **small["traffic"]}
        limits = small["limits"]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in reported]
    return Cell(workload, w["chips"], config, traffic, limits, e2e,
                per_layer)


def place_compile_cache() -> None:
    """The persistent cache at a fixed path inside the checkout, keeping
    every executable: the eager forward's per-op programs each compile
    in well under JAX's default one-second threshold."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Backend compiles, from jax.monitoring."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, dur: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Spans:
    """Host spans of the benchmark's own making: seconds per name, and a
    TraceAnnotation for the profiler's trace."""

    def __init__(self):
        self.total: dict[str, float] = {}
        self._wrapped: list[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.total[name] = (self.total.get(name, 0.0)
                                + time.perf_counter() - t0)

    def wrap(self, owner, attr: str, name: str):
        """Time every call of owner.attr under `name`."""
        orig = getattr(owner, attr)

        @functools.wraps(orig)
        def timed(*a, **kw):
            with self.span(name):
                return orig(*a, **kw)
        setattr(owner, attr, timed)
        self._wrapped.append((owner, attr, orig))

    def unwrap(self):
        while self._wrapped:
            owner, attr, orig = self._wrapped.pop()
            setattr(owner, attr, orig)


@dataclasses.dataclass
class Phase:
    """One scored phase inside the window, as a reader needs it."""
    n_layers: int
    n_heads: int
    mlp_dim: int
    candidates: int
    batch: int
    n_batches: int
    waves: list            # [(start_s, dispatch_s, ready_s)] per wave


@dataclasses.dataclass
class Record:
    """What a metric reader reads, once the window has closed."""
    cell: Cell
    window_s: float
    phases: list
    compiles: int
    trace: dict | None
    peak: dict

    @property
    def dims(self) -> dict:
        c = self.cell.config
        return dict(seq=c["seq_len"], d_model=c["hidden_size"],
                    d_head=c["head_dim"],
                    n_kv_heads=c["num_attention_heads"],
                    n_classes=c["n_classes"])


def read_metric(name: str, rec: Record):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)
