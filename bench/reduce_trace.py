"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is reduced to two lists on one clock (nanoseconds):

* device operations, per device plane: (start, end, name) of every event
  on the plane's "XLA Ops" line;
* host spans: (start, end, name) of the harness's `bench.*` annotations.

The traced window runs from the first host span's start to the last one's
end.  Busy time is the union of the device-operation intervals inside it,
idle time the rest; each idle gap is charged to the host span the host was
in at the gap's midpoint ("host" where it was between spans).  An
operation is named by its HLO name, trailing `.N` suffixes stripped
(`cim_mbiw`, `fusion`); a kernel is counted and timed by that name, and
each of its events is charged to the last host span begun before it.  Loops and calls (`while`,
`conditional`, `call`) hold other operations: they count towards busy
time but not in the breakdown of operations.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"(\.\d+)+$")

Interval = Tuple[float, float, str]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Interval]]
    spans: List[Interval]


CONTAINERS = ("while", "conditional", "call")


def base_name(text: str) -> str:
    """`%fusion.12 = f32[8]{0} fusion(...)` -> `fusion`."""
    name = text[1:].split(" = ", 1)[0] if text.startswith("%") else text
    return _SUFFIX.sub("", name)


def load_xplane(path: str) -> Trace:
    """Device operations and harness spans of one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = e.start_ns
                    ops.append((s, s + e.duration_ns, e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns
                        spans.append((s, s + e.duration_ns, e.name))
    return Trace(devices={k: v for k, v in devices.items() if v},
                 spans=sorted(spans))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(trace: Trace, kernels=("cim_mbiw",), top: int = 10) -> dict:
    """Busy and idle time, kernel counts and times, and the breakdown.

    Returns {"window_s", "busy_s" (mean over device planes), "kernels":
    {name: {"count", "seconds", "count_by_span": {span: n}}},
    "breakdown": {"device_ops": [[name, s]], "idle_gaps": [[span, s]]}}.
    """
    if not trace.spans:
        raise ValueError("trace holds no harness span")
    if not trace.devices:
        raise ValueError("trace holds no device operation")
    lo = min(s[0] for s in trace.spans)
    hi = max(s[1] for s in trace.spans)
    spans = sorted(trace.spans)
    starts = [s[0] for s in spans]

    def span_at(t):
        """The span the host was in at t ('host' between spans)."""
        i = bisect.bisect_right(starts, t) - 1
        return spans[i][2] if i >= 0 and spans[i][1] >= t else "host"

    def issued_by(t):
        """The last span that began by t: device work follows the host
        call that issued it, and may outlast it."""
        i = bisect.bisect_right(starts, t) - 1
        return spans[max(i, 0)][2]

    busy, op_time = [], collections.Counter()
    idle_by_span = collections.Counter()
    kern = {k: {"count": 0, "seconds": 0.0,
                "count_by_span": collections.Counter()} for k in kernels}
    for ops in trace.devices.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops
                  if e > lo and s < hi]
        merged = _union([(s, e) for s, e, _ in inside])
        busy.append(sum(e - s for s, e in merged))
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                idle_by_span[span_at((s + prev) / 2)] += (s - prev) * 1e-9
            prev = max(prev, e)
        for s, e, n in inside:
            b = base_name(n)
            if b not in CONTAINERS:
                op_time[b] += (e - s) * 1e-9
            if b in kern:
                kern[b]["count"] += 1
                kern[b]["seconds"] += (e - s) * 1e-9
                kern[b]["count_by_span"][issued_by(s)] += 1
    ndev = len(trace.devices)
    for k in kern.values():
        k["count_by_span"] = dict(k["count_by_span"])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / ndev * 1e-9,
        "kernels": kern,
        "breakdown": {
            "device_ops": [[n, s / ndev] for n, s in op_time.most_common(top)],
            "idle_gaps": [[n, s / ndev]
                          for n, s in idle_by_span.most_common(top)],
        },
    }
