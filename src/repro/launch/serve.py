"""Serving launcher: batched prefill + decode with KV/state caches.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --prompt-len 32 --gen-len 16 --batch 4

`--cim-mode engine` routes every CIM linear through the plan-once/serve-many
compiled-program runtime (runtime/program.py): the first prefill + decode
step builds one persistent program set in the module-level program cache
(one program per distinct layer geometry x batch bucket), and every later
decode step is a pure cache hit — zero re-planning, zero re-tracing.  The
launcher counts plans/traces across the decode loop and reports them;
`--assert-no-recompile` turns any post-warmup growth into a failure (the
serving-smoke CI job runs exactly that).  With `--engine-devices D > 1`
each layer's macro schedule additionally shards across a D-device mesh
(ShardingConfig) — on CPU-only hosts emulate the bank of macros with
XLA_FLAGS=--xla_force_host_platform_device_count=D.

`--inflight` switches the decode loop to continuous (in-flight) batching
over a slot-mapped KV cache (models/transformer.init_slot_cache): requests
admit (solo prefill, one scatter) and retire (cursor reset, gather-free)
between fused decode steps, `--batch` is the slot capacity, and in engine
mode every slot is its own activation-quantization segment
(CIMConfig.isolate_rows) so batchmates cannot perturb each other's
numerics.  Attention-cache families (dense/moe) only.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.cim_layers import CIMConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_serve_step
from repro.models import transformer as tf


def main(argv=None):
    """Parse `argv` (default: sys.argv) and serve.  The batched (non
    --inflight) path returns what it served — config, params, prompt,
    prefill logits, generated tokens, timings — so a caller in the same
    process can check the results."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--cim-mode", default="bypass",
                    choices=["bypass", "fakequant", "engine"])
    ap.add_argument("--engine-devices", type=int, default=0,
                    help="shard the engine-mode macro schedule across this "
                         "many devices (0 = no sharding; engine mode only)")
    ap.add_argument("--engine-axis", default="macro",
                    help="mesh axis name for the sharded engine dispatch")
    ap.add_argument("--assert-no-recompile", action="store_true",
                    help="fail if any decode step after the first re-plans "
                         "or re-traces the engine (the plan-once contract "
                         "of the compiled-program runtime)")
    ap.add_argument("--inflight", action="store_true",
                    help="continuous in-flight batching over a slot-mapped "
                         "KV cache: --batch slots, requests admit/retire "
                         "between fused decode steps (dense/moe only)")
    ap.add_argument("--requests", type=int, default=0,
                    help="total requests for --inflight (default 2x slots)")
    ap.add_argument("--precision-policy", default="off",
                    choices=["off", "mixed", "quality", "balanced",
                             "throughput"],
                    help="workload-adaptive precision serving demo "
                         "(engine + inflight only): calibrate a per-layer "
                         "sensitivity profile, plan a precision ladder, "
                         "and serve per-request operating points through "
                         "the in-flight scheduler ('mixed' alternates "
                         "quality/throughput requests)")
    ap.add_argument("--dtype", default=None,
                    choices=["bfloat16", "float32"],
                    help="activation dtype (default: the config's); the "
                         "engine-mode logits equal fakequant's bit for bit "
                         "at float32")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print(f"compile cache: {enable_compile_cache()}")

    if args.precision_policy != "off":
        if args.cim_mode != "engine" or not args.inflight:
            ap.error("--precision-policy requires --cim-mode engine "
                     "--inflight")
        return _run_precision_inflight(args)

    sharding = None
    if args.engine_devices:
        if args.cim_mode != "engine":
            ap.error("--engine-devices requires --cim-mode engine")
        from repro.runtime import ShardingConfig
        sharding = ShardingConfig(devices=args.engine_devices,
                                  axis=args.engine_axis)
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    cfg = cfg.replace(cim=CIMConfig(mode=args.cim_mode, max_gamma=2.0**16,
                                    sharding=sharding,
                                    isolate_rows=args.inflight))
    key = jax.random.PRNGKey(args.seed)
    params = tf.init_params(cfg, key)
    if args.inflight:
        return _run_inflight(ap, args, cfg, params)
    return _run_batched(args, cfg, params, key)


def _run_batched(args, cfg, params, key):
    """Batched prefill, then greedy decode of the whole batch per step."""
    max_len = args.prompt_len + args.gen_len + 8
    cache = tf.init_cache(cfg, args.batch, max_len=max_len)

    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    kwargs = {}
    if cfg.family == "audio":
        kwargs["encoder_frames"] = jax.random.normal(
            key, (args.batch, max_len, cfg.d_model))
        prompt = prompt[:, :1]
    if cfg.family == "vlm":
        kwargs["prefix_embeds"] = jax.random.normal(
            key, (args.batch, cfg.vision_tokens, cfg.d_model))

    t0 = time.time()
    logits, cache, _ = tf.forward(cfg, params, prompt, cache=cache, **kwargs)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    tok.block_until_ready()
    t_prefill = time.time() - t0
    print(f"prefill({prompt.shape[1]} tokens): {t_prefill:.2f}s")

    serve_step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    out = [tok]

    # warmup decode step: compiles the serve_step graph (and, in engine
    # mode, fills the persistent program set the remaining steps reuse)
    from repro.runtime import engine as rt_engine
    t_warm = 0.0
    if args.gen_len > 0:
        t0 = time.time()
        tok, cache = serve_step(params, cache, tok)
        tok.block_until_ready()
        out.append(tok)
        t_warm = time.time() - t0
    plans0, traces0 = rt_engine.PLAN_COUNT["n"], rt_engine.TRACE_COUNT["n"]

    steps = max(args.gen_len - 1, 0)
    t0 = time.time()
    for _ in range(steps):
        tok, cache = serve_step(params, cache, tok)
        out.append(tok)
    gen = jnp.concatenate(out, axis=1)
    gen.block_until_ready()
    dt = time.time() - t0
    d_plans = rt_engine.PLAN_COUNT["n"] - plans0
    d_traces = rt_engine.TRACE_COUNT["n"] - traces0
    if steps:
        print(f"decode {steps} steps: {dt:.2f}s "
              f"({steps * args.batch / dt:.1f} tok/s, "
              f"{dt / steps * 1e3:.1f} ms/step; warmup {t_warm:.2f}s)")
    print(f"decode recompiles after warmup: plans={d_plans} "
          f"traces={d_traces}")
    if args.cim_mode == "engine":
        from repro.runtime import program_cache_stats
        print(f"engine program cache: {program_cache_stats()}")
    if args.assert_no_recompile and (d_plans or d_traces):
        raise SystemExit(
            f"FAIL: decode loop re-entered the planner/compiler after "
            f"warmup (plans +{d_plans}, traces +{d_traces}) — the "
            f"plan-once/serve-many contract is broken")
    print("sample:", gen[0].tolist())
    return {"cfg": cfg, "params": params, "prompt": prompt,
            "prefill_logits": logits, "tokens": gen,
            "prefill_s": t_prefill, "warmup_s": t_warm, "decode_s": dt,
            "decode_steps": steps}


def _run_inflight(ap, args, cfg, params):
    """Continuous-batching decode loop: solo prefill into a slot-mapped
    cache, fused single-token decode over all slots, retire on budget —
    reporting per-request latency percentiles, throughput, and the
    post-warmup recompile counters (`--assert-no-recompile` gates them)."""
    if cfg.family not in ("dense", "moe"):
        ap.error(f"--inflight supports dense/moe families, not "
                 f"{cfg.family!r}")
    from repro.runtime import engine as rt_engine
    from repro.runtime.scheduler import SlotMap

    slots = args.batch
    max_len = args.prompt_len + args.gen_len + 8
    cache = tf.init_slot_cache(cfg, slots, max_len)
    rng = np.random.default_rng(args.seed)
    n_req = args.requests or 2 * slots
    # fixed-length prompts keep the prefill executable set at one trace;
    # generation budgets and arrivals are ragged (the in-flight dynamics)
    reqs = [{"uid": u,
             "prompt": rng.integers(0, cfg.vocab_size,
                                    size=args.prompt_len),
             "gen": int(rng.integers(1, args.gen_len + 1)),
             "arrival": int(rng.integers(0, args.gen_len))}
            for u in range(n_req)]
    reqs.sort(key=lambda r: r["arrival"])

    def prefill(prompt):
        c1 = tf.init_cache(cfg, 1, max_len=max_len)
        logits, c1, _ = tf.forward(cfg, params, prompt[None], cache=c1)
        return c1, jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)

    @jax.jit
    def step(params, cache, tok):
        # explicit (B, 1) positions: every slot decodes at its own offset
        pos = cache["pos"][:, None]
        logits, cache, _ = tf.forward(cfg, params, tok[:, None],
                                      positions=pos, cache=cache)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache

    smap = SlotMap(slots)
    live, done, queue = {}, [], list(reqs)
    cur = jnp.zeros((slots,), jnp.int32)
    clock, decode_steps, snap, t_decode = 0, 0, None, 0.0
    t_start = time.time()
    while queue or live:
        while queue and smap.n_free and queue[0]["arrival"] <= clock:
            r = queue.pop(0)
            s = smap.alloc()
            c1, tok = prefill(jnp.asarray(r["prompt"], jnp.int32))
            cache = tf.write_slot_cache(cache, s, c1)
            cur = cur.at[s].set(tok[0])
            r.update(slot=s, admitted=clock, tokens=[int(tok[0])])
            if len(r["tokens"]) >= r["gen"]:
                smap.free(s)
                cache = tf.free_slot_cache(cache, s)
                r["finished"] = clock
                done.append(r)
            else:
                live[s] = r
        if live:
            t0 = time.time()
            nxt, cache = step(params, cache, cur)
            nxt = jax.device_get(nxt)
            t_decode += time.time() - t0
            decode_steps += 1
            if snap is None:        # post-warmup recompile baseline
                snap = (rt_engine.PLAN_COUNT["n"],
                        rt_engine.TRACE_COUNT["n"])
            for s in sorted(live):
                r = live[s]
                r["tokens"].append(int(nxt[s]))
                cur = cur.at[s].set(int(nxt[s]))
                if len(r["tokens"]) >= r["gen"]:
                    smap.free(s)
                    cache = tf.free_slot_cache(cache, s)
                    r["finished"] = clock
                    del live[s]
                    done.append(r)
        clock += 1

    lat = np.asarray([r["finished"] - r["arrival"] for r in done], float)
    toks = sum(len(r["tokens"]) for r in done)
    wall = time.time() - t_start
    print(f"inflight: {len(done)} requests, {toks} tokens, "
          f"{decode_steps} fused steps over {slots} slots in {wall:.2f}s")
    print(f"latency steps p50/p99: {np.percentile(lat, 50):.1f}/"
          f"{np.percentile(lat, 99):.1f}; "
          f"decode {toks / t_decode:.1f} tok/s" if t_decode else "")
    d_plans = rt_engine.PLAN_COUNT["n"] - (snap or (0, 0))[0]
    d_traces = rt_engine.TRACE_COUNT["n"] - (snap or (0, 0))[1]
    if snap is not None:
        print(f"decode recompiles after warmup: plans={d_plans} "
              f"traces={d_traces}")
        if args.assert_no_recompile and (d_plans or d_traces):
            raise SystemExit(
                f"FAIL: in-flight loop re-entered the planner/compiler "
                f"after warmup (plans +{d_plans}, traces +{d_traces})")
    print("sample:", done[0]["tokens"])


def _run_precision_inflight(args):
    """Workload-adaptive precision serving demo: calibrate, plan the
    ladder, serve mixed per-request operating points in flight.

    Pipeline (the PR 10 tentpole end to end): (1) `precision.calibrate`
    profiles the toy decode-LM's four projection GEMMs; (2)
    `precision.assign` turns quality budgets into per-layer (r_in, r_w)
    assignments; (3) `CIMDecodeLM.toy(points=...)` compiles + binds one
    block stack per operating point over the SAME weights; (4) the
    in-flight scheduler fuses same-point requests per decode step.  The
    demo then proves the serving contracts: zero post-warmup recompiles
    (under --assert-no-recompile), every fused request bit-identical to
    its solo decode at the same point, and the per-point projected
    TOPS/W echoed next to measured token counts."""
    from repro.precision import DEFAULT_BUDGETS, assign, calibrate
    from repro.core import mapping
    from repro.runtime import engine as rt_engine
    from repro.runtime.engine import EngineConfig
    from repro.runtime.program import program_cache_stats
    from repro.runtime.scheduler import (CIMDecodeLM, InflightScheduler,
                                         Request, decode_sequential)

    d, depth, vocab, d_ff = 48, 2, 61, 96
    base = (8, 4)
    specs = (mapping.LayerSpec(m=8, k=d, n=3 * d, r_in=base[0],
                               r_w=base[1]),
             mapping.LayerSpec(m=8, k=d, n=d, r_in=base[0], r_w=base[1]),
             mapping.LayerSpec(m=8, k=d, n=2 * d_ff, r_in=base[0],
                               r_w=base[1]),
             mapping.LayerSpec(m=8, k=d_ff, n=d, r_in=base[0],
                               r_w=base[1]))
    t0 = time.time()
    prof = calibrate(specs, EngineConfig(), n_trials=2, batch=4,
                     seed=args.seed, label="serve-demo")
    names = (["quality", "throughput"] if args.precision_policy == "mixed"
             else [args.precision_policy])
    points = {}
    for name in names:
        asg, delta = assign(prof, specs, DEFAULT_BUDGETS[name])
        points[name] = asg
        print(f"precision: point {name!r} -> "
              f"{[(ri, rw) for ri, rw in asg]} "
              f"(predicted quality delta {delta:.4f})")
    print(f"precision: profile + plan in {time.time() - t0:.1f}s")

    key = jax.random.PRNGKey(args.seed)
    model = CIMDecodeLM.toy(key, d=d, depth=depth, vocab=vocab,
                            r_in=base[0], r_w=base[1], points=points)
    rng = np.random.default_rng(args.seed)
    n_req = args.requests or 2 * args.batch
    gen_hi = max(args.gen_len, 2)
    reqs = [Request(uid=u,
                    prompt=tuple(int(t) for t in rng.integers(
                        0, vocab, size=max(args.prompt_len, 1))),
                    max_new_tokens=int(rng.integers(1, gen_hi + 1)),
                    point=names[u % len(names)])
            for u in range(n_req)]

    # warmup: dispatch one decode per operating point at every bucket
    # extent the scheduler can reach — the executable set the measured
    # run must then serve entirely from cache
    buckets = model.bound.program.buckets
    ext_set = sorted({min(buckets.bucket_for(x), args.batch)
                      for x in range(1, args.batch + 1)})
    st_full = model.init_state(args.batch)
    for nm in names:
        for e_w in ext_set:
            rows = jax.tree_util.tree_map(lambda a: a[:e_w], st_full)
            model.step_rows(rows, jnp.zeros((e_w,), jnp.int32), None,
                            None, point=nm)
    plans0 = rt_engine.PLAN_COUNT["n"]
    traces0 = rt_engine.TRACE_COUNT["n"]

    sched = InflightScheduler(model, capacity=args.batch)
    out = sched.run([(int(rng.integers(0, gen_hi)), r) for r in reqs])
    m = sched.metrics()
    d_plans = rt_engine.PLAN_COUNT["n"] - plans0
    d_traces = rt_engine.TRACE_COUNT["n"] - traces0

    bad = [r.uid for r in reqs if out[r.uid] != decode_sequential(model, r)]
    print(f"inflight: {int(m['requests'])} requests, "
          f"{int(m['tokens'])} tokens, {int(m['decode_steps'])} fused "
          f"steps over {args.batch} slots "
          f"({m['tokens_per_s']:.1f} tok/s decode)")
    for name in names:
        op = sched.point_report(name)["operating_point"]
        toks = m["tokens_by_point"].get(name, 0.0)
        print(f"point {name!r}: {int(toks)} tokens served, projected "
              f"{op['tops_per_w']:.2f} TOPS/W")
    print(f"decode recompiles after warmup: plans={d_plans} "
          f"traces={d_traces}")
    print(f"engine program cache: {program_cache_stats()}")
    print("per-request bit-exactness vs solo decode: "
          + ("PASS" if not bad else f"FAIL {bad}"))
    if bad:
        raise SystemExit("FAIL: fused decode diverged from solo decode "
                         f"for uids {bad}")
    if args.assert_no_recompile and (d_plans or d_traces):
        raise SystemExit(
            f"FAIL: precision serving re-entered the planner/compiler "
            f"after warmup (plans +{d_plans}, traces +{d_traces})")


if __name__ == "__main__":
    main()
