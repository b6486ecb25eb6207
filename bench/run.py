"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's program from the checkout's `src/`, makes weights and
inputs on the device from `--seed`, warms every shape the cell uses (all
of that is `setup_s`), serves the cell's traffic in a closed loop for
`--seconds`, then compares a sample of what the window served with the
plain reference.  `--trace 1` records a profiler trace of a stretch of the
window and reports the cell's per-layer metrics in place of its
end-to-end ones.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`, each compared number beside its limit;
the last lines of standard error repeat the checks.  The run exits
non-zero and prints no result when JAX finds no TPU, fewer chips than the
cell asks for, or no program sources beside `bench/`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import harness  # noqa: E402
import reduce_trace  # noqa: E402
import traffic as tr  # noqa: E402
from cim import least_seconds  # noqa: E402

# the traced stretch: from a third of the window, this long at most
TRACE_SECONDS = 2.0
KERNEL = "cim_mbiw"


def enable_compile_cache(root=harness.ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached however fast it compiled."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileEvents:
    """Counts JAX's compile, trace and cache events."""

    def __init__(self):
        import jax
        self.n = collections.Counter()
        jax.monitoring.register_event_listener(
            lambda event, **_: self.n.update([event]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _secs, **__: self.n.update([event]))

    def snapshot(self) -> int:
        """Compiles, cache loads and traces so far, plus the engine's
        plan and trace counters."""
        from repro.runtime import engine
        n = self.n
        return (n["/jax/core/compile/backend_compile_duration"]
                + n["/jax/compilation_cache/cache_hits"]
                + n["/jax/core/compile/jaxpr_trace_duration"]
                + engine.PLAN_COUNT["n"] + engine.TRACE_COUNT["n"])


def per_layer(spec: dict, cell: str, record: dict) -> dict:
    """Every per-layer metric of the cell that its reader finds."""
    out = {}
    for m in harness.cell_metrics(spec, cell, "per_layer"):
        value = harness.load_module(harness.metric_path(m["name"])).read(
            record)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def trace_record(rec: harness.Recorder, trace_dir: str, peaks: dict,
                 compiles: int, step: str) -> dict:
    """What the per-layer readers read: the reduced trace, the units and
    least work inside the traced stretch, the server's step unit, the
    peaks, the compile count."""
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    reduced = reduce_trace.reduce(reduce_trace.load_xplane(files[0]),
                                  kernels=(KERNEL,))
    units = collections.Counter(u[0] for u in rec.in_trace())
    work = collections.Counter()
    for u in rec.in_trace():
        work.update(u[3])
    return {"trace": reduced, "units": dict(units), "work": dict(work),
            "step": step, "peaks": peaks, "kernel": KERNEL,
            "compiles_in_window": compiles}


def measure(cfg: dict, model, trfc: dict, seed: int, seconds: float,
            trace: bool, devices, server_module=None) -> dict:
    """One run of one cell: set up, serve the window, read, check.
    `server_module` replaces the configuration's server module (tests
    break the timed path with it)."""
    server_module = server_module or harness.load_module(
        harness.server_path(cfg["server"]))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rec = harness.Recorder(trace_dir, trace_from=seconds / 3,
                           trace_seconds=min(TRACE_SECONDS, seconds / 3))
    server = server_module.Server(cfg, model, trfc, rec)
    events = CompileEvents()
    server.build()
    server.load(seed)
    setup_s = time.perf_counter() - T_START
    before = events.snapshot()
    rec.open_window(seconds)
    server.run(rec.window[1])
    rec.close_window()
    rec.stop_trace()
    compiles = events.snapshot() - before
    res = server.results(rec.window)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    out = {"setup_s": setup_s, "res": res, "peak": peak,
           "compiles": compiles, "cache": dict(
               hits=events.n["/jax/compilation_cache/cache_hits"],
               misses=events.n["/jax/compilation_cache/cache_misses"])}
    if trace:
        try:
            if rec.traced is None:
                raise RuntimeError("the window ended before the trace began")
            out["record"] = trace_record(
                rec, trace_dir, harness.peaks_for(devices[0].device_kind),
                compiles, server.STEP)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    server.release()
    out["checks"] = server.check()
    return out


def limits_for(cell: str) -> dict:
    return harness.load_json(harness.BENCH / "limits" / f"{cell}.json")


def verdict(checks: dict, limits: dict) -> tuple:
    """(correct, {name: {value, limit}}) of a run's compared numbers."""
    table = {name: {"value": checks[name], "limit": lim["limit"]}
             for name, lim in limits.items()}
    ok = checks.get("compared", 0) > 0 and all(
        t["value"] <= t["limit"] for t in table.values())
    return ok, table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell, centry = harness.find_cell(spec, args.workload)
    cfg, model_path = harness.config_files(centry)
    trfc = tr.load(cell["traffic"])
    src = harness.ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"bench: {cell['chips']} chips asked, {len(devs)} found",
              file=sys.stderr)
        return 1
    devs = devs[:cell["chips"]]
    print(f"device: {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    model = harness.load_module(model_path)
    out = measure(cfg, model, trfc, args.seed, args.seconds,
                  bool(args.trace), devs)
    print(f"setup_s: {out['setup_s']:.3f}; compile cache "
          f"{out['cache']['hits']} hits, {out['cache']['misses']} misses; "
          f"compiles in window: {out['compiles']}", flush=True)
    print(f"samples: {json.dumps(out['res']['samples'])}", flush=True)
    print(f"compared: {json.dumps(out['checks'])}", flush=True)
    if args.trace:
        r = out["record"]
        k = r["trace"]["kernels"][KERNEL]
        least, bound = least_seconds(r["work"].get("cim_ops", 0),
                                     r["work"].get("cim_bytes", 0),
                                     r["peaks"])
        print(f"traced: {r['trace']['window_s']:.6f} s, units "
              f"{json.dumps(r['units'])}; {KERNEL}: {k['count']} calls, "
              f"{k['seconds']:.6f} s on the device, least {least:.9f} s "
              f"({bound}-bound)", flush=True)
    line = result_line(spec, cell["name"], out, bool(args.trace), devs)
    for name, t in line["checks"].items():
        print(f"check {name}: {t['value']!r} (limit {t['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


def result_line(spec: dict, cell: str, out: dict, trace: bool,
                devices) -> dict:
    """The result's JSON object, its compared numbers last."""
    res = out["res"]
    if trace:
        metrics = per_layer(spec, cell, out["record"])
    else:
        values = dict(res["metrics"], setup_s=out["setup_s"])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in harness.cell_metrics(spec, cell, "end_to_end")
                   if m["name"] in values}
    correct, table = verdict(out["checks"], limits_for(cell))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["peak"]}
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = out["record"]["trace"]["busy_s"]
        device["window_s"] = out["record"]["trace"]["window_s"]
        line["breakdown"] = out["record"]["trace"]["breakdown"]
    line["checks"] = table
    return line


if __name__ == "__main__":
    sys.exit(main())
