"""BENCHMARK.json against the contract's shape, every name resolved to
its files, a cell added by data files alone found by the harness, and
the result line's keys."""
import json
import os
import re
import shutil
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import harness  # noqa: E402
import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_spec_keys_and_names():
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] == 1
        assert 0 < len(w["why"]) <= 200
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  harness.load_spec()["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    spec = harness.load_spec()
    w, c = harness.find_cell(spec, cell)
    cfg, module = harness.config_files(c)
    assert module.exists() and harness.server_path(cfg["server"]).exists()
    assert harness.traffic_path(w["traffic"]).exists()
    assert (harness.BENCH / "limits" / f"{cell}.json").exists()
    e2e = harness.cell_metrics(spec, cell, "end_to_end")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    layer = harness.cell_metrics(spec, cell, "per_layer")
    assert layer and all(m["moves"] in names for m in layer)
    for m in layer:
        assert harness.metric_path(m["name"]).exists()


def test_a_cell_added_as_data_files_is_found(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_spec()
    spec["workloads"].append({
        "name": "olmo1b.decode.b32", "config": "olmo-1b",
        "traffic": "chat_b32", "chips": 1, "why": "a smaller batch"})
    spec["per_layer"].append({
        "name": "busy_s.olmo", "unit": "s", "better": "higher",
        "source": "device_trace", "layer": "device",
        "moves": "tokens_per_s", "workloads": ["olmo1b.decode.b32"]})
    for m in spec["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("olmo1b.decode.b32")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = tmp_path / "bench"
    chat = json.loads((bench / "traffic" / "chat_b64.json").read_text())
    (bench / "traffic" / "chat_b32.json").write_text(
        json.dumps(dict(chat, batch=32)))
    (bench / "metrics" / "busy_s.py").write_text(
        "def read(r):\n    return r['trace']['busy_s']\n")
    got = harness.load_spec(tmp_path)
    cell, config = harness.find_cell(got, "olmo1b.decode.b32")
    assert config["name"] == "olmo-1b"
    import traffic as tr
    assert tr.load(cell["traffic"], bench)["batch"] == 32
    names = [m["name"] for m in
             harness.cell_metrics(got, "olmo1b.decode.b32", "per_layer")]
    assert names == ["busy_s.olmo"]
    reader = harness.load_module(harness.metric_path("busy_s.olmo", bench))
    assert reader.read({"trace": {"busy_s": 1.5}}) == 1.5


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


def _out(trace):
    out = {"setup_s": 12.5, "peak": 1 << 30,
           "res": {"metrics": {"images_per_s": 4.0e4}, "attempted": 9,
                   "failed": 0, "samples": {}},
           "checks": {"output_gap": 0.0, "compared": 8192}}
    if trace:
        red = {"window_s": 2.0, "busy_s": 1.5,
               "kernels": {"cim_mbiw": {"count": 70, "seconds": 0.2,
                                        "count_by_span": {"bench.batch": 70}}},
               "breakdown": {"device_ops": [["cim_mbiw", 0.2]],
                             "idle_gaps": [["bench.batch.fetch", 0.5]]}}
        out["record"] = {"trace": red, "units": {"batch": 10},
                         "step": "batch",
                         "work": {"model_ops": 1e9, "cim_ops": 1e9,
                                  "cim_bytes": 1e6},
                         "peaks": harness.peaks_for("TPU v5 lite"),
                         "kernel": "cim_mbiw", "compiles_in_window": 0}
    return out


@pytest.mark.parametrize("trace", [False, True])
def test_result_line_holds_the_contract_keys(trace):
    spec = harness.load_spec()
    line = run.result_line(spec, "lenet5.b10000.r8w4", _out(trace), trace,
                           [_Dev()])
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(line) == keys + ["checks"]
    assert line["correct"] is True
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert line["device"]["busy_s"] == 1.5
        assert set(line["metrics"]) == {
            "idle_share.lenet", "mfu.lenet", "cim_mbiw_roofline.lenet",
            "cim_calls_per_step.lenet", "compiles_in_window.lenet"}
        assert line["metrics"]["cim_calls_per_step.lenet"]["value"] == 7.0
    else:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["checks"]["output_gap"]["limit"] > 0
    json.dumps(line)


def test_a_number_over_its_limit_is_not_correct():
    ok, table = run.verdict({"output_gap": 1.0, "compared": 5},
                            {"output_gap": {"limit": 0.5}})
    assert not ok and table == {"output_gap": {"value": 1.0, "limit": 0.5}}
    ok, _ = run.verdict({"output_gap": 0.0, "compared": 0},
                        {"output_gap": {"limit": 0.5}})
    assert not ok                   # nothing compared is not correct
