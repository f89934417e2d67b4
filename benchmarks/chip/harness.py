"""What every cell shares: finding a cell's files by name, seeds, host
spans, the window, the run record, metric readers and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its files:

  configs/<config>.json   sizes as run, source, cuts (listed in ``configs``)
  traffic/<traffic>.json  the mix; ``driver`` names drivers/<driver>.py
  limits/<cell>.json      the limit of every number the check compares
  metrics/<metric>.py     one reader per metric, ``read(run, ctx)``

So a new cell of an existing driver is data files alone.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRACED = "bench.traced"      # the span around the traced part of a window
ROOT = os.path.dirname(os.path.dirname(HERE))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict           # configs/<config>.json
    traffic: Dict          # traffic/<traffic>.json
    limits: Dict           # limits/<cell>.json
    bench: Dict            # BENCHMARK.json

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def load_bench(root: str = ROOT) -> Dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, bench: Dict, root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, cfg["file"])),
        traffic=_load_json(os.path.join(HERE, "traffic",
                                        w["traffic"] + ".json")),
        limits=_load_json(os.path.join(HERE, "limits", name + ".json")),
        bench=bench)


def sub_seeds(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for each use, from any whole ``seed``."""
    words = np.random.SeedSequence(int(seed)).generate_state(4)
    keys = ("weights", "data", "traffic", "check")
    return {k: int(w) & 0x7FFFFFFF for k, w in zip(keys, words)}


class Spans:
    """Harness spans around each call into a layer: host-clock intervals,
    also written into the profiler trace when the run is traced."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.items: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.traced:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> List[float]:
        return [e - s for n, s, e in self.items if n == name]


@dataclass
class RunRecord:
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    counters: Dict[str, float] = field(default_factory=dict)
    requests: List[Dict[str, Any]] = field(default_factory=list)
    checks: Dict[str, float] = field(default_factory=dict)
    memory_peak_bytes: int = 0
    trace: Optional[Dict[str, Any]] = None


@dataclass
class Context:
    """What a driver is given."""
    cell: Cell
    sizes: Dict                 # the configuration's sizes as run
    program_cfg: Any            # repro ModelConfig of the same sizes
    seeds: Dict[str, int]
    seconds: float
    traced: bool
    t_start: float              # process start, for set-up time
    workdir: str
    spans: Spans
    smoke: bool = False
    control: bool = False       # calibration: also read the control
    device_kind: str = ""
    cache_events: Dict[str, int] = field(default_factory=dict)
    tracing: bool = False       # the profiler is on now
    _trace: Dict[str, Any] = field(default_factory=dict)

    @property
    def peaks(self) -> Dict[str, float]:
        import peaks
        return peaks.chip_peaks(self.device_kind)

    def trace_poll(self, elapsed: float) -> float:
        """In a traced run, turn the profiler on once ``elapsed`` (seconds
        into the window) reaches the mix's ``trace_from_s`` and off once it
        reaches ``trace_from_s + trace_s``: a whole window holds more device
        events than the profiler keeps. Drivers call this between calls
        into the program. Returns the seconds it took (writing the trace
        out takes a minute for a serving cell); the driver moves its
        window's clock on by as much, as if the world had paused."""
        if not self.traced or self._trace.get("done"):
            return 0.0
        import jax
        t0 = time.perf_counter()
        mix = self.cell.traffic
        if not self.tracing and elapsed >= mix["trace_from_s"]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(os.path.join(self.workdir, "trace"),
                                     profiler_options=opts)
            self._trace["span"] = jax.profiler.TraceAnnotation(TRACED)
            self._trace["span"].__enter__()
            self.tracing = True
        elif self.tracing and elapsed >= mix["trace_from_s"] + mix["trace_s"]:
            self._stop_trace()
        return time.perf_counter() - t0

    def _stop_trace(self) -> None:
        import jax
        self._trace["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing, self._trace["done"] = False, True

    @contextlib.contextmanager
    def window(self, rec: RunRecord):
        """Set-up ends here; traced runs trace a part of this block (see
        ``trace_poll``)."""
        rec.setup_s = time.perf_counter() - self.t_start
        log(f"set-up {rec.setup_s:.3f} s; window opens")
        before = dict(self.cache_events)
        try:
            with self.spans("bench.window"):
                yield
        finally:
            if self.tracing:
                self._stop_trace()
        compiled = self.cache_events.get("compiles", 0) - before.get(
            "compiles", 0)
        rec.counters["compiles_in_window"] = compiled
        log(f"window closed after {rec.window_s:.3f} s; "
            f"{compiled} compilations inside it")
        if self._trace.get("done"):
            from tracefold import from_xplane, reduce
            trace_dir = os.path.join(self.workdir, "trace")
            t = from_xplane(trace_dir)
            log(f"trace planes: {t['planes']}")
            rec.trace = reduce(t)
            shutil.rmtree(trace_dir, ignore_errors=True)

    def count(self, rec: RunRecord, **traced) -> None:
        """Add to counters of the work done while the profiler is on."""
        if self.tracing:
            for k, v in traced.items():
                rec.counters[k] = rec.counters.get(k, 0) + v


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def cell_metrics(bench: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics a cell reports: end-to-end ones untraced, per-layer ones
    traced, each where its ``workloads`` list names the cell (or, without
    the list, where the cell reports the metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def read_metrics(metrics: List[Dict], rec: RunRecord, ctx: Context
                 ) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                             "metric_" + m["name"].replace(".", "_"))
        value = reader.read(rec, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def percentile(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values), q)) if values else None


def mean_span(ctx: Context, name: str) -> Optional[float]:
    d = ctx.spans.durations(name)
    return float(np.mean(d)) if d else None


def idle_pct(rec: RunRecord) -> Optional[float]:
    if rec.trace is None:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])


def device_s(rec: RunRecord, span: str) -> Optional[float]:
    if rec.trace is None:
        return None
    s = rec.trace["device_s_by_span"].get(span)
    return s if s else None


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------

def judge(checks: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit; a number without a limit is
    an error of the benchmark, not a pass."""
    out = {}
    for name, value in checks.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in this cell's limits")
        out[name] = {"value": float(value), "limit": float(limits[name])}
    return out


def split_checks(checks: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """The numbers compared, by whose reading they are: the program's under
    "", the control's or a planted fault's (named "<who>.<number>") under
    "<who>". Each is judged against the same limits."""
    out: Dict[str, Dict[str, float]] = {}
    for key, value in checks.items():
        who, _, number = key.rpartition(".")
        out.setdefault(who, {})[number] = value
    return out


def passed(judged: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in judged.values())


def workdir() -> str:
    """A scratch directory under TMPDIR, removed by the caller."""
    return tempfile.mkdtemp(prefix="bench-")


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   counted: np.ndarray) -> float:
    """Largest |norm(program leaf) - norm(reference leaf)| over the larger
    of the reference leaf's norm and the median counted leaf's."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    med = float(np.median(ref[counted]))
    denom = np.maximum(ref, med)
    return float(np.max(np.abs(prog - ref)[counted] / denom[counted]))


def counted_leaves(grad_leaf: np.ndarray) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's norm."""
    g = np.asarray(grad_leaf, float)
    return g >= 1e-3 * float(np.median(g))
