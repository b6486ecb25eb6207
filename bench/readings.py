"""Readings that a cell's correctness limit is set from, in one process.

    python bench/readings.py --workload <cell> --seeds 12 --seconds 12

For each seed it makes that seed's weights and traffic, serves a short
window through the timed path at the cell's own size, and reads the
compared number of the program and of the control: the plain reference
computed in bfloat16 in the program's place (the nearest precision below
the configuration's float32).  The program is built and compiled once.
One JSON line per seed, then the largest program reading and the smallest
control reading.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

import harness
import run
import traffic as tr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell, centry = harness.find_cell(spec, args.workload)
    cfg, model_path = harness.config_files(centry)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: JAX finds no TPU", file=sys.stderr)
        return 1
    run.enable_compile_cache()
    model = harness.load_module(model_path)
    module = harness.load_module(harness.server_path(cfg["server"]))
    server = module.Server(cfg, model, tr.load(cell["traffic"]),
                             harness.Recorder())
    server.build()
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        server.rec = harness.Recorder()
        server.load(seed)
        server.run(server.rec.open_window(args.seconds))
        server.rec.close_window()
        res = server.results(server.rec.window)
        checks = server.check(control=True)
        rows.append(checks)
        print(json.dumps({"seed": seed, **checks, **res["metrics"]}),
              flush=True)
    for c in [k for k in rows[0] if "control_" in k]:
        n = c.replace("control_", "", 1)
        print(f"{n}: program max {max(r[n] for r in rows)!r}, control min "
              f"{min(r[c] for r in rows)!r}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
