"""Device time of a profiler trace charged to the program's own layers.

The program names the device ops of its hot path with `jax.named_scope`
(the taxonomy is listed once, in `src/repro/runtime/engine.py`): `cim.*`
for the stages of the CIM engine, `lm.*` for the decoder LM around it.  A
scope is compile-time metadata: it prefixes the `op_name` of every HLO op
traced inside it.  A TPU trace does not carry that metadata: an event of
the "XLA Ops" line is named by its HLO instruction (`%fusion.5 = ...`)
and runs inside an event of the "XLA Modules" line named by its module
(`jit__exec_jit(<fingerprint>)`), with no op_name stat.  The op_name is
read from the module's optimized HLO text, which XLA dumps as it compiles
(`hlo_op_names`).  Around every bucketed dispatch the program also opens
a host span, `repro.serve`.

Rules, on the clock and in the window of `reduce_trace.reduce`:

* a device op is charged to the innermost `cim.*`/`lm.*` component of its
  `op_name`, or to `(unscoped)` (XLA records a fusion under its root op's
  `op_name`; ops XLA inserts itself, such as layout copies, have none);
  loops and calls (`while`, `conditional`, `call`) hold other ops and are
  not charged;
* its time is charged to the harness span that issued it, as
  `reduce_trace` charges a kernel's events: the last `bench.*` span begun
  by the op's start.  Program spans never take that part;
* an idle gap is charged to the innermost host span open at its midpoint,
  `repro.*` or `bench.*` ("host" between spans).

`reduce_trace.reduce` is untouched by any of this: it reads `bench.*`
spans and op names alone.

Run one traced run of a cell, as `bench/run.py --trace 1` does, and print
after run.py's own lines one `scopes:` JSON line with the reduction, the
per-step readings, scope coverage, `cim.kernel` beside `cim_mbiw` time and
the tracing overhead:

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

The run compiles every program afresh, with the persistent compilation
cache off, so that XLA dumps the HLO of exactly what the trace runs:
its `setup_s` is a cold one.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

import reduce_trace as rt

SCOPE_PREFIXES = ("cim.", "lm.")
SPAN_PREFIXES = ("bench.", "repro.")
UNSCOPED = "(unscoped)"
MODULES_LINE = "XLA Modules"

# per-step readings: name -> which scopes they sum
READINGS = {
    "cim_glue_ms_per_step": lambda s: (s.startswith("cim.")
                                       and s not in ("cim.kernel",
                                                     "cim.bind")),
    "weight_bind_ms_per_step": lambda s: s == "cim.bind",
    "attention_ms_per_step": lambda s: s in ("lm.attention", "lm.kv_write"),
}

Op = Tuple[float, float, str, str]          # start, end, HLO name, op_name


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]
    spans: List[rt.Interval]                # bench.* and repro.* spans


def scope_of(op_name: str) -> str:
    """The innermost taxonomy component of an op_name path."""
    inner = [p for p in op_name.split("/") if p.startswith(SCOPE_PREFIXES)]
    return inner[-1] if inner else UNSCOPED


_HLO_LINE = re.compile(r'^\s*(?:ROOT )?%([^ ]+) = .*op_name="([^"]*)"')


def hlo_op_names(dump_dir: str) -> Dict[str, Dict[str, str]]:
    """{HLO module name: {op: op_name}} from the optimized modules XLA
    dumps (`--xla_dump_to=<dir> --xla_dump_hlo_as_text`).  Two modules of
    one name share an entry: a cell runs one executable of each name in
    its window."""
    out: Dict[str, Dict[str, str]] = {}
    for f in glob.glob(os.path.join(dump_dir, "*.after_optimizations.txt")):
        module = os.path.basename(f).split(".")[1]
        names = out.setdefault(module, {})
        with open(f) as fh:
            for line in fh:
                m = _HLO_LINE.match(line)
                if m:
                    names[m.group(1)] = m.group(2)
    return out


def load_xplane(path: str, hlo_dump: Optional[str] = None) -> Trace:
    """Device ops with their op_name, and the harness's and the program's
    host spans, of one `.xplane.pb` file.  `hlo_dump` is the directory of
    XLA's dumps of the traced executables (no dump: every op_name empty)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    names = hlo_op_names(hlo_dump) if hlo_dump else {}
    devices: Dict[str, List[Op]] = {}
    spans: List[rt.Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for line in plane.lines
                             if line.name == MODULES_LINE
                             for e in line.events)
            starts = [m[0] for m in modules]
            for line in plane.lines:
                if line.name != rt.OPS_LINE:
                    continue
                for e in line.events:
                    s = e.start_ns
                    # the module running at s, without its `(fingerprint)`
                    i = bisect.bisect_right(starts, s) - 1
                    module = modules[i][2].split("(")[0] if i >= 0 else ""
                    op = e.name[1:].split(" = ", 1)[0] \
                        if e.name.startswith("%") else e.name
                    ops.append((s, s + e.duration_ns, e.name,
                                names.get(module, {}).get(op, "")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        s = e.start_ns
                        spans.append((s, s + e.duration_ns, e.name))
    return Trace(devices={k: v for k, v in devices.items() if v},
                 spans=sorted(spans))


def _innermost(spans: List[rt.Interval], starts: List[float], t: float
               ) -> str:
    """The latest-begun span open at t ('host' when none is)."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i][1] >= t:
            return spans[i][2]
        i -= 1
    return "host"


def reduce(trace: Trace, top: int = 10) -> dict:
    """Device time by scope, idle time by innermost host span.

    Returns {"scopes": {scope: {"seconds", "count", "seconds_by_span":
    {bench span: s}}}, "device_scopes": [[scope, s]] (top by time),
    "idle_program": [[span, s]], "unscoped_ops": [[HLO name, s]] (top
    ops charged to no scope), "coverage": share of the charged device time
    that some scope holds}.  Times are means over device planes."""
    bench = sorted(s for s in trace.spans
                   if s[2].startswith(rt.SPAN_PREFIX))
    if not bench:
        raise ValueError("trace holds no harness span")
    if not trace.devices:
        raise ValueError("trace holds no device operation")
    lo, hi = min(s[0] for s in bench), max(s[1] for s in bench)
    bench_starts = [s[0] for s in bench]
    spans = sorted(trace.spans)
    starts = [s[0] for s in spans]

    def issued_by(t):
        i = bisect.bisect_right(bench_starts, t) - 1
        return bench[max(i, 0)][2]

    seconds, count = collections.Counter(), collections.Counter()
    by_span = collections.defaultdict(collections.Counter)
    unscoped, idle = collections.Counter(), collections.Counter()
    for ops in trace.devices.values():
        inside = [(max(s, lo), min(e, hi), n, o) for s, e, n, o in ops
                  if e > lo and s < hi]
        prev = lo
        for s, e in rt._union([(s, e) for s, e, _, _ in inside]) + [(hi, hi)]:
            if s > prev:
                idle[_innermost(spans, starts, (s + prev) / 2)] += \
                    (s - prev) * 1e-9
            prev = max(prev, e)
        for s, e, n, o in inside:
            b = rt.base_name(n)
            if b in rt.CONTAINERS:
                continue
            scope, dt = scope_of(o), (e - s) * 1e-9
            seconds[scope] += dt
            count[scope] += 1
            by_span[scope][issued_by(s)] += dt
            if scope == UNSCOPED:
                unscoped[b] += dt
    ndev = len(trace.devices)
    total = sum(seconds.values())
    return {
        "scopes": {k: {"seconds": v / ndev, "count": count[k] / ndev,
                       "seconds_by_span": {sp: t / ndev for sp, t
                                           in by_span[k].items()}}
                   for k, v in seconds.items()},
        "device_scopes": [[k, v / ndev] for k, v in seconds.most_common(top)],
        "idle_program": [[k, v / ndev] for k, v in idle.most_common(top)],
        "unscoped_ops": [[k, v / ndev]
                         for k, v in unscoped.most_common(top)],
        "coverage": (total - seconds[UNSCOPED]) / total if total else 0.0,
    }


def per_step_ms(scopes: dict, units: dict, step: str, reading: str
                ) -> Optional[float]:
    """Device ms per unit of the server's step in the scopes `reading`
    sums, over the time charged to `bench.<step>` and `bench.<step>.fetch`.

    None where the trace holds no unit of the step, or no scoped op at all
    (a program without scopes); 0.0 where it holds scoped ops but none of
    these."""
    n = units.get(step, 0)
    if not n or not any(k != UNSCOPED for k in scopes):
        return None
    want = READINGS[reading]
    spans = (f"bench.{step}", f"bench.{step}.fetch")
    total = sum(v["seconds_by_span"].get(sp, 0.0)
                for k, v in scopes.items() if want(k) for sp in spans)
    return 1e3 * total / n


def overhead(units: List[tuple], traced: Tuple[float, float], step: str
             ) -> Optional[float]:
    """Mean host time of a step (its `step` and `step.fetch` spans)
    inside the traced stretch over the mean outside it: what tracing costs
    the step, from the Recorder's own records."""
    lo, hi = traced

    def mean(rows):
        n = sum(1 for u in rows if u[0] == step)
        return sum(u[2] - u[1] for u in rows
                   if u[0] in (step, f"{step}.fetch")) / n if n else None

    on = mean([u for u in units if lo <= u[1] and u[2] <= hi])
    off = mean([u for u in units if u[2] < lo or u[1] > hi])
    return on / off if on and off else None


def summary(red: dict, units: dict, step: str, kernel_s: float,
            step_overhead: Optional[float]) -> dict:
    """The `scopes:` line: per-step readings, coverage, kernel agreement,
    overhead, and the reduction's lists."""
    n = units.get(step, 0)
    kernel_scope_s = red["scopes"].get("cim.kernel", {}).get("seconds", 0.0)
    return {
        "step": step, "units": n,
        "readings": {r: per_step_ms(red["scopes"], units, step, r)
                     for r in READINGS},
        "ms_per_step": {k: 1e3 * sum(v["seconds_by_span"].get(sp, 0.0)
                                     for sp in (f"bench.{step}",
                                                f"bench.{step}.fetch")) / n
                        for k, v in red["scopes"].items()} if n else {},
        "coverage": red["coverage"],
        "cim.kernel_s": kernel_scope_s, "cim_mbiw_s": kernel_s,
        "tracing_overhead": step_overhead,
        "device_scopes": red["device_scopes"],
        "unscoped_ops": red["unscoped_ops"],
        "idle_program": red["idle_program"],
    }


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    hlo_dump = tempfile.mkdtemp(prefix="bench-hlo-")
    # set before JAX starts its backend: every program compiles here, and
    # XLA dumps it
    os.environ["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={hlo_dump}",
         "--xla_dump_hlo_as_text"])
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import run
    got = {}
    record = run.trace_record

    def trace_record(rec, trace_dir, peaks, compiles, step):
        """run.trace_record, and the same trace reduced by scope."""
        out = record(rec, trace_dir, peaks, compiles, step)
        (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb"))
        got["line"] = summary(
            reduce(load_xplane(path, hlo_dump)), out["units"], step,
            out["trace"]["kernels"][run.KERNEL]["seconds"],
            overhead(rec.units, rec.traced, step))
        return out

    run.trace_record = trace_record
    try:
        rc = run.main(argv + ["--trace", "1"])
    finally:
        shutil.rmtree(hlo_dump, ignore_errors=True)
    if rc == 0:
        print("scopes: " + json.dumps(got["line"]), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
