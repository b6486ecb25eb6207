"""The trace reduction: busy union, idle share, kernel count and time,
idle gaps charged to host spans; and reading a trace recorded on the CPU."""
import glob
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import harness  # noqa: E402
import reduce_trace as rt  # noqa: E402

MS = 1_000_000          # nanoseconds


def _trace():
    # host: prepare 0-10, decode 10-20, decode.fetch 20-60, decode 60-70,
    # decode.fetch 70-100 (ms).  device: overlapping and nested ops.
    spans = [(0, 10 * MS, "bench.prepare"), (10 * MS, 20 * MS, "bench.decode"),
             (20 * MS, 60 * MS, "bench.decode.fetch"),
             (60 * MS, 70 * MS, "bench.decode"),
             (70 * MS, 100 * MS, "bench.decode.fetch")]
    ops = [(12 * MS, 30 * MS, "fusion.3"), (25 * MS, 40 * MS, "cim_mbiw.1"),
           (40 * MS, 45 * MS, "cim_mbiw.2"), (75 * MS, 95 * MS, "cim_mbiw"),
           (-5 * MS, 2 * MS, "copy.1")]
    return rt.Trace(devices={"/device:TPU:0": ops}, spans=spans)


def test_busy_union_and_idle():
    r = rt.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    # union: [0,2] + [12,45] + [75,95] = 2 + 33 + 20 ms
    assert r["busy_s"] == pytest.approx(0.055)
    m = harness.load_module(harness.metric_path("idle_share.olmo"))
    assert m.read({"trace": r}) == pytest.approx(45.0)


def test_kernel_count_time_and_spans():
    k = rt.reduce(_trace())["kernels"]["cim_mbiw"]
    assert k["count"] == 3
    assert k["seconds"] == pytest.approx(0.040)
    assert k["count_by_span"] == {"bench.decode.fetch": 3}
    rec = {"trace": rt.reduce(_trace()), "units": {"decode": 2},
           "step": "decode", "kernel": "cim_mbiw"}
    m = harness.load_module(harness.metric_path("cim_calls_per_step.olmo"))
    assert m.read(rec) == pytest.approx(1.5)
    # the CNN's reader is the same file, counting over served batches
    m = harness.load_module(harness.metric_path("cim_calls_per_step.lenet"))
    assert m.read(dict(rec, step="batch")) is None
    assert m.read(dict(rec, step="batch", units={"batch": 3})) == 0.0


def test_idle_gaps_charged_to_host_spans():
    gaps = dict(rt.reduce(_trace())["breakdown"]["idle_gaps"])
    # idle: 2-12 (mid 7: prepare), 45-75 (mid 60: decode at its start),
    # 95-100 (mid 97.5: decode.fetch)
    assert gaps["bench.prepare"] == pytest.approx(0.010)
    assert gaps["bench.decode"] == pytest.approx(0.030)
    assert gaps["bench.decode.fetch"] == pytest.approx(0.005)
    ops = dict(rt.reduce(_trace())["breakdown"]["device_ops"])
    assert ops["cim_mbiw"] == pytest.approx(0.040)
    assert ops["fusion"] == pytest.approx(0.018)


def test_reduce_refuses_an_empty_trace():
    with pytest.raises(ValueError):
        rt.reduce(rt.Trace(devices={}, spans=[(0, 1, "bench.batch")]))
    with pytest.raises(ValueError):
        rt.reduce(rt.Trace(devices={"/device:TPU:0": [(0, 1, "x")]},
                           spans=[]))


def test_reads_harness_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    rec = harness.Recorder(str(tmp_path), trace_from=0.0, trace_seconds=60)
    rec.open_window(60.0)
    rec.boundary()                      # starts the profiler
    for _ in range(3):
        with rec.span("batch", {"model_ops": 5}):
            f(x).block_until_ready()
    rec.stop_trace()
    assert [u[0] for u in rec.in_trace()] == ["batch"] * 3
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    t = rt.load_xplane(path)
    assert [s[2] for s in t.spans] == ["bench.batch"] * 3
    assert t.devices == {}              # the CPU has no TPU plane
