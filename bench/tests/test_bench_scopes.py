"""The reduction of a trace by program scope (bench/scopes.py): the
innermost-scope and fusion-root rules, `(unscoped)`, program spans nested
in harness spans, the per-step readings, and the existing reduction left
as it was on the same trace."""
import glob
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import harness  # noqa: E402
import reduce_trace as rt  # noqa: E402
import scopes as sc  # noqa: E402

MS = 1_000_000          # nanoseconds
STEP = "jit(serve_step)/while/body/closed_call/lm.layer/jit(_exec_jit)"


def _ops():
    # (start, end, HLO name, op_name) in ms
    return [
        (12, 14, "slice_convert_fusion.2", STEP + "/cim.bind/convert"),
        (14, 18, "cim_mbiw.1", STEP + "/jit(cim_mbiw_matmul_planes)"
         "/cim.kernel/cond/branch_1_fun/cim_mbiw"),
        # a fusion whose root XLA recorded under cim.zp_fold, holding ops
        # of other scopes: charged to its root's scope
        (18, 21, "fusion.7", STEP + "/cim.zp_fold/reduce_sum"),
        (21, 22, "copy-done", ""),                     # XLA-inserted
        (22, 30, "while.3", "jit(serve_step)/while"),  # container
        (30, 33, "fusion.9", "jit(serve_step)/while/body/closed_call"
         "/lm.layer/lm.attention/lm.kv_write/dynamic_update_slice"),
        (33, 35, "fusion.10", "jit(serve_step)/while/body/closed_call"
         "/lm.layer/lm.attention/exp"),
        (62, 66, "divide_add_fusion", STEP + "/cim.recombine/div"),
        (66, 67, "fusion.11", "jit(serve_step)/lm.head/dot_general"),
    ]


def _spans(program: bool):
    spans = [(0, 10, "bench.prepare"), (10, 20, "bench.decode"),
             (20, 60, "bench.decode.fetch"), (60, 70, "bench.decode"),
             (70, 100, "bench.decode.fetch")]
    if program:
        spans += [(11, 19, "repro.serve"), (40, 50, "repro.serve")]
    return spans


def _trace(program: bool = True) -> sc.Trace:
    return sc.Trace(
        devices={"/device:TPU:0": [(s * MS, e * MS, n, o)
                                   for s, e, n, o in _ops()]},
        spans=sorted((s * MS, e * MS, n) for s, e, n in _spans(program)))


def _view(trace: sc.Trace) -> rt.Trace:
    """What `reduce_trace.load_xplane` reads from the same file."""
    return rt.Trace(devices={k: [(s, e, n) for s, e, n, _ in v]
                             for k, v in trace.devices.items()},
                    spans=[s for s in trace.spans
                           if s[2].startswith(rt.SPAN_PREFIX)])


def test_innermost_scope_wins():
    assert sc.scope_of(STEP + "/cim.zp_fold/reduce_sum") == "cim.zp_fold"
    assert sc.scope_of("a/lm.layer/lm.attention/lm.kv_write/x") \
        == "lm.kv_write"
    assert sc.scope_of("jit(serve_step)/while/body") == sc.UNSCOPED
    assert sc.scope_of("") == sc.UNSCOPED


def test_ops_charged_by_their_recorded_op_name():
    red = sc.reduce(_trace())
    s = red["scopes"]
    # a fusion goes to the scope of the op_name XLA recorded for it, not
    # to what its HLO name suggests
    assert s["cim.zp_fold"]["seconds"] == pytest.approx(0.003)
    assert s["cim.bind"]["seconds"] == pytest.approx(0.002)
    assert s["cim.kernel"]["seconds"] == pytest.approx(0.004)
    assert s["lm.kv_write"]["seconds"] == pytest.approx(0.003)
    assert s["lm.attention"]["seconds"] == pytest.approx(0.002)
    assert s["lm.head"]["count"] == 1
    # containers hold other ops and are not charged
    assert sum(v["count"] for v in s.values()) == len(_ops()) - 1


def test_unscoped_ops_are_listed():
    red = sc.reduce(_trace())
    assert red["scopes"][sc.UNSCOPED]["seconds"] == pytest.approx(0.001)
    assert red["unscoped_ops"] == [["copy-done", pytest.approx(0.001)]]
    charged = 2 + 4 + 3 + 1 + 3 + 2 + 4 + 1
    assert red["coverage"] == pytest.approx((charged - 1) / charged)


def test_program_spans_nest_inside_harness_spans():
    red = sc.reduce(_trace())
    # device time goes to the bench span that issued it, never to the
    # program span open inside it
    assert red["scopes"]["cim.kernel"]["seconds_by_span"] \
        == {"bench.decode": pytest.approx(0.004)}
    assert red["scopes"]["lm.kv_write"]["seconds_by_span"] \
        == {"bench.decode.fetch": pytest.approx(0.003)}
    assert all(not sp.startswith("repro.")
               for v in red["scopes"].values() for sp in v["seconds_by_span"])
    # an idle gap goes to the innermost host span open at its midpoint:
    # 0-12 (at 6: prepare), 35-62 (at 48.5: the repro.serve inside
    # decode.fetch), 67-100 (at 83.5: decode.fetch)
    idle = dict(red["idle_program"])
    assert idle == {"bench.prepare": pytest.approx(0.012),
                    "repro.serve": pytest.approx(0.027),
                    "bench.decode.fetch": pytest.approx(0.033)}
    # the harness's reduction charges the same gap to decode.fetch
    gaps = dict(rt.reduce(_view(_trace()))["breakdown"]["idle_gaps"])
    assert gaps["bench.decode.fetch"] == pytest.approx(0.060)


def _record(trace: rt.Trace) -> dict:
    return {"trace": rt.reduce(trace), "units": {"decode": 2},
            "step": "decode", "kernel": "cim_mbiw",
            "work": {"model_ops": 7e9, "cim_ops": 3e9, "cim_bytes": 2e6},
            "peaks": harness.peaks_for("TPU v5 lite"),
            "compiles_in_window": 0}


def test_existing_reduction_unchanged_by_program_spans_and_scopes():
    plain = sc.Trace(
        devices={k: [(s, e, n, "") for s, e, n, _ in v]
                 for k, v in _trace(program=False).devices.items()},
        spans=_trace(program=False).spans)
    a = rt.reduce(_view(plain))
    b = rt.reduce(_view(_trace(program=True)))
    assert a == b
    for key in ("window_s", "busy_s", "kernels", "breakdown"):
        assert a[key] == b[key]
    spec = harness.load_spec()
    for m in spec["per_layer"]:
        if "olmo1b.decode.b64" not in m.get("workloads", ()):
            continue
        read = harness.load_module(harness.metric_path(m["name"])).read
        assert read(_record(_view(plain))) \
            == read(_record(_view(_trace()))), m["name"]


def _fake_profile(tmp_path, program: bool):
    """A ProfileData stand-in holding _ops and _spans as a TPU trace holds
    them: each op event named by its HLO instruction, inside one module
    event; with `program` set, XLA's dump of that module (under
    `tmp_path`) gives each op its op_name."""
    ev = types.SimpleNamespace
    text = ["HloModule jit_serve_step"]
    ops = []
    for i, (s, e, n, o) in enumerate(_ops()):
        name = f"%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p.{i})"
        ops.append(ev(name=name, start_ns=s * MS, duration_ns=(e - s) * MS,
                      stats=[]))
        meta = f', metadata={{op_name="{o}" stack_frame_id=1}}' if o else ""
        text.append(f"  {name}{meta}")
    if program:
        (tmp_path / "module_0003.jit_serve_step.cl_7.after_optimizations"
         ".txt").write_text("\n".join(text) + "\n")
    host = [ev(name=n, start_ns=s * MS, duration_ns=(e - s) * MS,
               stats=[]) for s, e, n in _spans(program)]
    host.append(ev(name="ThunkExecutor::Execute", start_ns=0,
                   duration_ns=MS, stats=[]))
    module = ev(name="jit_serve_step(4769134653304966812)", start_ns=0,
                duration_ns=100 * MS, stats=[])
    return ev(planes=[
        ev(name="/device:TPU:0", lines=[
            ev(name=sc.MODULES_LINE, events=[module]),
            ev(name=rt.OPS_LINE, events=ops)]),
        ev(name="/host:CPU", lines=[ev(name="python", events=host)])])


@pytest.mark.parametrize("program", [False, True],
                         ids=["parent", "scoped"])
def test_one_file_two_readers_agree(monkeypatch, tmp_path, program):
    import jax.profiler
    fake = _fake_profile(tmp_path, program)
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: fake))
    old = rt.load_xplane("trace.xplane.pb")
    new = sc.load_xplane("trace.xplane.pb", str(tmp_path))
    assert _view(new) == old
    assert rt.reduce(_view(new)) == rt.reduce(old)
    # the harness reads the same numbers as from the trace built in memory
    assert rt.reduce(old) == rt.reduce(_view(_trace(program=False)))
    # the dump gives each op the op_name of its HLO instruction
    got = [o for *_, o in new.devices["/device:TPU:0"]]
    assert got == ([o for *_, o in _ops()] if program else [""] * len(got))
    scoped = [k for k in sc.reduce(new)["scopes"] if k != sc.UNSCOPED]
    assert bool(scoped) is program


def test_readings_per_step():
    red = sc.reduce(_trace())
    units = {"decode": 2}
    # cim.* but kernel and bind: zp_fold 3 ms + recombine 4 ms, 2 steps
    assert sc.per_step_ms(red["scopes"], units, "decode",
                          "cim_glue_ms_per_step") == pytest.approx(3.5)
    assert sc.per_step_ms(red["scopes"], units, "decode",
                          "weight_bind_ms_per_step") == pytest.approx(1.0)
    # lm.attention 2 ms + lm.kv_write 3 ms, charged to decode.fetch
    assert sc.per_step_ms(red["scopes"], units, "decode",
                          "attention_ms_per_step") == pytest.approx(2.5)


def test_readings_zero_without_their_scopes_none_without_a_step():
    red = sc.reduce(_trace())
    s = {k: v for k, v in red["scopes"].items() if k != "cim.bind"}
    # the bind hoisted out of the step reads 0, not nothing
    assert sc.per_step_ms(s, {"decode": 2}, "decode",
                          "weight_bind_ms_per_step") == 0.0
    assert sc.per_step_ms(red["scopes"], {"batch": 3}, "decode",
                          "weight_bind_ms_per_step") is None
    # a program without scopes (the parent's) gives no reading
    unscoped = {sc.UNSCOPED: red["scopes"][sc.UNSCOPED]}
    assert sc.per_step_ms(unscoped, {"decode": 2}, "decode",
                          "cim_glue_ms_per_step") is None


def test_tracing_overhead_from_recorder_units():
    units = [("decode", 0.0, 1.0, {}), ("decode.fetch", 1.0, 2.0, {}),
             ("decode", 10.0, 12.0, {}), ("decode.fetch", 12.0, 12.5, {}),
             ("decode", 13.0, 14.0, {}), ("decode.fetch", 14.0, 15.0, {}),
             ("decode", 20.0, 21.0, {}), ("decode.fetch", 21.0, 22.0, {})]
    # inside 9.5-16: (2.5 + 2) / 2 = 2.25 a step; outside (2 + 2) / 2 = 2
    assert sc.overhead(units, (9.5, 16.0), "decode") == pytest.approx(1.125)
    assert sc.overhead(units, (30.0, 31.0), "decode") is None


def test_summary_reports_kernel_scope_beside_kernel():
    red = sc.reduce(_trace())
    line = sc.summary(red, {"decode": 2}, "decode", 0.004, 1.01)
    assert line["cim.kernel_s"] == pytest.approx(line["cim_mbiw_s"])
    assert line["readings"]["cim_glue_ms_per_step"] == pytest.approx(3.5)
    assert line["ms_per_step"]["cim.kernel"] == pytest.approx(2.0)
    assert line["tracing_overhead"] == 1.01


def test_reads_program_spans_from_a_cpu_trace(tmp_path):
    import jax
    import numpy as np

    from repro.core import mapping
    from repro.runtime.program import BatchBuckets, compile_program

    prog = compile_program([mapping.LayerSpec(m=4, k=16, n=8)],
                           buckets=BatchBuckets(min_bucket=4))
    bound = prog.bind(prog.init_params(jax.random.PRNGKey(0)))
    x = jax.random.uniform(jax.random.PRNGKey(1), (3, 16))
    np.asarray(bound.serve(x))
    rec = harness.Recorder(str(tmp_path), trace_from=0.0, trace_seconds=60)
    rec.open_window(60.0)
    rec.boundary()                      # starts the profiler
    for _ in range(2):
        with rec.span("batch"):
            y = bound.serve(x)
        with rec.span("batch.fetch"):
            np.asarray(y)
    rec.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    t = sc.load_xplane(path)
    names = [s[2] for s in t.spans]
    assert names.count("repro.serve") == 2
    assert names.count("bench.batch") == 2
    for s, e, n in t.spans:
        if n == "repro.serve":          # inside the batch that served it
            assert any(b[0] <= s and e <= b[1] for b in t.spans
                       if b[2] == "bench.batch")
    # the harness's own reading of the same file sees no program span
    assert [s[2] for s in rt.load_xplane(path).spans] \
        == [n for n in names if n.startswith("bench.")]
