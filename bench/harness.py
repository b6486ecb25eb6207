"""What every cell of the benchmark shares: the spec, finding a cell's files
by name, the peak table, host spans, and the statistics of a run.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found here by the name that
`BENCHMARK.json` gives it:

    bench/configs/<config>.json   sizes, precision, source, server name
    bench/configs/<config>.py     weights from the seed, least work from
                                  shapes, the plain reference
    bench/traffic/<mix>.json      parameters read by bench/traffic.py
    bench/servers/<server>.py     how a kind of model is served in a window
    bench/metrics/<metric>.py     a reader of one per-layer metric; a
                                  metric `a.b` falls back to `a.py`

so a later change adds a cell by adding files and entries only.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(root: Path = ROOT) -> dict:
    """BENCHMARK.json of the checkout at `root`."""
    return load_json(root / "BENCHMARK.json")


def load_module(path: Path, name: Optional[str] = None):
    """Import a Python file by path (file names may hold '-' and '.')."""
    name = name or "bench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, name: str) -> tuple:
    """(cell entry, config entry) of the cell called `name`."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells are {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def config_files(config_entry: dict, root: Path = ROOT) -> tuple:
    """(sizes dict, path of the module beside it) of one configuration."""
    path = root / config_entry["file"]
    return load_json(path), path.with_suffix(".py")


def traffic_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "traffic" / f"{name}.json"


def server_path(name: str, bench: Path = BENCH) -> Path:
    return bench / "servers" / f"{name}.py"


def metric_path(name: str, bench: Path = BENCH) -> Path:
    """The reader of metric `name`: metrics/<name>.py, or for a metric
    split by what it moves (`mfu.olmo`) the shared metrics/<mfu>.py."""
    own = bench / "metrics" / f"{name}.py"
    if own.exists():
        return own
    return bench / "metrics" / f"{name.split('.')[0]}.py"


def cell_metrics(spec: dict, cell: str, kind: str) -> List[dict]:
    """The entries of `spec[kind]` that cell `cell` reports.

    An end-to-end metric with a `workloads` key belongs to those cells,
    one without to every cell.  A per-layer metric with the key belongs to
    those cells; one without to every cell that reports what it moves."""
    def listed(m, own):
        return own if "workloads" not in m else cell in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if listed(m, True)]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if listed(m, m["moves"] in moved)]


def peaks_for(device_kind: str, bench: Path = BENCH) -> dict:
    """The peak table's row for `device_kind`; an unknown kind is an error,
    never a default."""
    table = load_json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table)}")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """The q-th percentile of all `values`, linearly interpolated."""
    import numpy as np
    if len(values) == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(values, np.float64), q))


class Recorder:
    """Host spans of the measured window, and the switch of the profiler.

    A server wraps each unit of work in `span(kind, work)`; the span is a
    `TraceAnnotation` named `bench.<kind>` in the profiler's trace and a
    (kind, start, end, work) record here.  Between units the server calls
    `boundary()`, where a traced run starts or stops the profiler, so the
    traced stretch holds whole units only."""

    def __init__(self, trace_dir: Optional[str] = None,
                 trace_from: float = 0.0, trace_seconds: float = 0.0):
        self.units: List[tuple] = []
        self.trace_dir = trace_dir
        self.trace_from = trace_from
        self.trace_seconds = trace_seconds
        self.traced: Optional[tuple] = None     # (start, end) host clock
        self._tracing_since: Optional[float] = None
        self.window: Optional[tuple] = None

    def open_window(self, seconds: float) -> float:
        """Start the measured window now; returns its deadline."""
        t0 = time.perf_counter()
        self.window = (t0, t0 + seconds)
        return t0 + seconds

    def close_window(self) -> tuple:
        """End the window now.  A server starts no unit of its traffic (a
        batch) after the deadline and finishes the one in flight, so the
        window holds whole units and a rate does not swing with where the
        deadline cuts one."""
        self.window = (self.window[0], time.perf_counter())
        return self.window

    @contextlib.contextmanager
    def span(self, kind: str, work: Optional[Dict] = None):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{kind}"):
            yield
        self.units.append((kind, t0, time.perf_counter(), work or {}))

    def boundary(self) -> None:
        if self.trace_dir is None or self.window is None:
            return
        now = time.perf_counter()
        if self._tracing_since is None and self.traced is None \
                and now >= self.window[0] + self.trace_from:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._tracing_since = time.perf_counter()
        elif self._tracing_since is not None \
                and now >= self._tracing_since + self.trace_seconds:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self._tracing_since is None:
            return
        import jax
        end = time.perf_counter()
        jax.profiler.stop_trace()
        self.traced = (self._tracing_since, end)
        self._tracing_since = None

    def in_trace(self) -> List[tuple]:
        """Units wholly inside the traced stretch."""
        if self.traced is None:
            return []
        lo, hi = self.traced
        return [u for u in self.units if lo <= u[1] and u[2] <= hi]
