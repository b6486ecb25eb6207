"""Pallas cim_mbiw kernel micro-benchmark (interpret mode on CPU: checks
dispatch overhead + correctness at benchmark shapes; wall-clock here is NOT
TPU performance — the TPU projection is the roofline analysis).

Sweeps the macro's precision operating points (r_in x r_w) through the
precision-specialized kernel variants, reporting per-precision wall-clock,
achieved integer-op rate, and bit-exactness against the oracle — the
software analogue of the paper's Fig. 22 sweep.  The scaling sweep
additionally shards the engine across 1/2/4/8 devices.  Run as a script
with JAX_PLATFORMS=cpu, the process requests 8 fake CPU devices via
XLA_FLAGS *before* jax initializes, so CPU-only CI exercises the
multi-macro dispatch; on a chip the sweep uses the devices that exist and
prints how many."""
import os
import time

# must precede the first jax import
if __name__ == "__main__" and os.environ.get("JAX_PLATFORMS") == "cpu":
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import digital_ref as dr
from repro.core.hw import DEFAULT_MACRO
from repro.kernels.cim_mbiw import ops
from repro.kernels.cim_mbiw.ref import cim_matmul_ref

PRECISIONS = [(r_in, r_w) for r_in in (1, 2, 4, 8) for r_w in (1, 2, 4)]


def _case(m, k, n, r_in, r_w, r_out=8, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.randint(kx, (m, k), 0, 2 ** r_in).astype(jnp.int32)
    w = dr.quantize_weight_odd(
        jax.random.randint(kw, (k, n), -(2 ** r_w - 1), 2 ** r_w), r_w)
    gamma = jnp.full((n,), 16.0)
    beta = jnp.zeros((n,))
    cfg = DEFAULT_MACRO
    units = cfg.units_for_rows(min(k, cfg.n_rows))
    g0 = dr.adc_gain_factor(r_in, r_w, r_out, units * cfg.rows_per_unit,
                            cfg.swing_efficiency(units), cfg.alpha_adc())
    return x, w, gamma, beta, g0


def bench(m, k, n, r_in=8, r_w=4, r_out=8, iters=3):
    x, w, gamma, beta, g0 = _case(m, k, n, r_in, r_w, r_out, seed=m + k + n)
    out = ops.cim_matmul(x, w, gamma, beta, r_in=r_in, r_out=r_out, g0=g0)
    out.block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        out = ops.cim_matmul(x, w, gamma, beta, r_in=r_in, r_out=r_out,
                             g0=g0)
        out.block_until_ready()
    t_kernel = (time.time() - t0) / iters

    ref = cim_matmul_ref(x, w, gamma, beta, g0=g0, r_out=r_out)
    match = bool(jnp.all(out == ref))
    return t_kernel * 1e6, match


def bench_precision_sweep(m=128, k=1152, n=64, iters=3):
    """Per-precision throughput through the dispatch table (Fig. 22 sweep)."""
    rows = []
    for r_in, r_w in PRECISIONS:
        prec = ops.KernelPrecision(r_in, r_w, 8)
        fn = ops.kernel_variant(prec, bm=128, bn=128, bk=256)
        x, w, gamma, beta, g0 = _case(m, k, n, r_in, r_w, seed=r_in + r_w)
        out = fn(x, w, gamma, beta, g0)
        out.block_until_ready()
        t0 = time.time()
        for _ in range(iters):
            fn(x, w, gamma, beta, g0).block_until_ready()
        us = (time.time() - t0) / iters * 1e6
        ref = cim_matmul_ref(x, w, gamma, beta, g0=g0, r_out=8)
        match = bool(jnp.all(out == ref))
        gops = 2.0 * m * k * n / (us * 1e-6) / 1e9
        rows.append((r_in, r_w, prec.n_planes, us, gops, match))
    return rows


def bench_conv_sweep(batch=4, h=14, w=14, c_in=16, c_out=32, iters=2):
    """Conv front-end sweep: a 3x3 conv layer through the engine's im2col
    streaming + kernel dispatch at each precision point, checked bit-exact
    against the digital conv reference (engine.reference)."""
    from repro.core.mapping import conv_layer_spec
    from repro.runtime import CIMInferenceEngine

    rows = []
    for r_in, r_w in PRECISIONS:
        spec = conv_layer_spec(batch, h, w, c_in, c_out, kh=3, kw=3,
                               stride=1, padding=1, r_in=r_in, r_w=r_w)
        eng = CIMInferenceEngine([spec], activations=["none"])
        params = eng.init_params(jax.random.PRNGKey(r_in + r_w))
        x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(0),
                                          (batch, h, w, c_in)))
        out = eng(params, x)
        out.block_until_ready()
        t0 = time.time()
        for _ in range(iters):
            eng(params, x).block_until_ready()
        us = (time.time() - t0) / iters * 1e6
        match = bool(jnp.all(out == eng.reference(params, x)))
        macs = 2.0 * spec.m * spec.k * spec.n
        gops = macs / (us * 1e-6) / 1e9
        rows.append((r_in, r_w, us, gops, match))
    return rows


def bench_noise_sweep(batch=8, n_trials=2, scales=(0.0, 1.0, 2.0)):
    """Noise-injected engine mode: LeNet on pseudo_mnist through the fast
    Pallas path at scaled noise operating points, Monte-Carlo trials each.

    Reports per-scale wall-clock per trial, mean accuracy over trials, and
    determinism (trial 0 re-run under the same seed must be bit-identical)
    — the software analogue of the paper's Sec. V.A noise studies."""
    from repro.core.cim_layers import CIMConfig
    from repro.core.noise_model import NoiseConfig
    from repro.data.pseudo_mnist import make_dataset
    from repro.models.cnn import init_lenet, lenet_engine, lenet_params_list

    _, _, xte, yte = make_dataset(n_train=1, n_test=batch)
    imgs = jnp.asarray(xte)[..., None]
    labels = jnp.asarray(yte)
    base = NoiseConfig()
    rows = []
    for scale in scales:
        noise = base.replace(enabled=scale > 0,
                             thermal_rms_lsb8=base.thermal_rms_lsb8 * scale,
                             sa_sigma_v=base.sa_sigma_v * scale)
        cim = CIMConfig(mode="engine", r_in=4, r_w=2, noise=noise)
        params = lenet_params_list(init_lenet(jax.random.PRNGKey(0),
                                              cim=cim))
        eng = lenet_engine(batch, cim=cim)
        key = jax.random.PRNGKey(7)
        if noise.enabled:
            eng.monte_carlo(params, imgs, key, 1).block_until_ready()  # warm
            t0 = time.time()
            logits = eng.monte_carlo(params, imgs, key, n_trials)
            logits.block_until_ready()
            us = (time.time() - t0) / n_trials * 1e6
            redo = eng(params, imgs, jax.random.split(key, n_trials)[0])
            det = bool(jnp.all(logits[0] == redo))
        else:
            eng(params, imgs).block_until_ready()
            t0 = time.time()
            logits = eng(params, imgs)[None]
            logits.block_until_ready()
            us = (time.time() - t0) * 1e6
            det = bool(jnp.all(logits[0] == eng(params, imgs)))
        acc = float(jnp.mean(jnp.argmax(logits, -1) == labels[None, :]))
        rows.append((scale, us, acc, det))
    return rows


def bench_scaling_sweep(devices=(1, 2, 4, 8), iters=3):
    """Weak/strong-scaling of the sharded engine (ISSUE 4 tentpole).

    Strong scaling: a fixed 2-layer schedule (col-tile-rich first layer,
    rows-sharded second) at constant global work, sharded over D devices.
    Weak scaling: the GEMM-row extent grows with D (64 rows per device).
    Every point is checked bit-exact against the single-device engine.
    Wall-clock on emulated CPU devices measures dispatch plumbing, not
    macro performance — the numbers are for trend/regression tracking."""
    from repro.core.mapping import LayerSpec
    from repro.runtime import CIMInferenceEngine, EngineConfig, ShardingConfig

    def build(m, d):
        specs = [LayerSpec(m=m, k=576, n=256, r_in=4, r_w=4),   # 4 col tiles
                 LayerSpec(m=m, k=256, n=32, r_in=4, r_w=4)]    # rows kind
        cfg = EngineConfig()
        if d:
            cfg = cfg.replace(sharding=ShardingConfig(devices=d))
        return CIMInferenceEngine(specs, cfg)

    def run(eng, params, x, n=iters):
        eng(params, x).block_until_ready()          # compile
        t0 = time.time()
        for _ in range(n):
            eng(params, x).block_until_ready()
        return (time.time() - t0) / n * 1e6

    avail = len(jax.devices())
    m_strong = 256
    base = build(m_strong, 0)
    params = base.init_params(jax.random.PRNGKey(0))
    x_strong = jax.nn.relu(
        jax.random.normal(jax.random.PRNGKey(1), (m_strong, 576)))
    t_serial = run(base, params, x_strong)
    y_serial = jax.device_get(base(params, x_strong))

    rows = []
    for d in devices:
        if d > avail:
            rows.append((d, None, None, None, None))
            continue
        eng = build(m_strong, d)
        t_strong = run(eng, params, x_strong)
        match = bool((jax.device_get(eng(params, x_strong))
                      == y_serial).all())
        # weak scaling: 64 GEMM rows per device
        m_weak = 64 * d
        engw = build(m_weak, d)
        pw = engw.init_params(jax.random.PRNGKey(0))
        xw = jax.nn.relu(
            jax.random.normal(jax.random.PRNGKey(1), (m_weak, 576)))
        t_weak = run(engw, pw, xw)
        # the weak-scaling shapes exercise per-d rows-kind padding the
        # strong point does not — bit-check them too
        match &= bool((jax.device_get(engw(pw, xw))
                       == jax.device_get(build(m_weak, 0)(pw, xw))).all())
        eff = engw.perf_report()["total"]["parallel_efficiency"]
        rows.append((d, t_strong, t_weak, eff, match))
    return t_serial, rows


def bench_serving(batch=4, d=256, layers=3, steps=24, out_json=None):
    """Plan-once/serve-many vs the legacy per-call path (ISSUE 5).

    A decode-shaped workload (a `layers`-deep stack of d x d CIM linears at
    batch `batch` — one LM decode step per call) served two ways:

      * legacy: re-plan the network and re-enter run_network every call —
        what serve.py paid per token before the compiled-program runtime
        (the jit cache still hits on the equal plan, so this isolates the
        per-call planning + weight-quantization-in-graph overhead);
      * program: one compiled CIMProgram, weights pre-bound
        (`prog.bind(params)`), every call a bucket-cache hit.

    Both paths must agree bit-exactly.  Returns a row dict (per-call
    latency, tokens/s, speedup) and, when `out_json` is set, writes it as
    BENCH_serving.json for the serving-smoke CI job."""
    import json
    import warnings

    from repro.core.mapping import LayerSpec
    from repro.runtime import compile_program
    from repro.runtime import engine as rt

    specs = [LayerSpec(m=batch, k=d, n=d, r_in=4, r_w=2)
             for _ in range(layers)]
    acts = ["relu"] * (layers - 1) + ["none"]
    prog = compile_program(specs, activations=acts)
    params = prog.init_params(jax.random.PRNGKey(0))
    bound = prog.bind(params)
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(1), (batch, d)))

    def legacy_call():
        plan = rt.plan_network(specs, rt.EngineConfig(), acts)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return rt.run_network(plan, params, x)

    y_prog = bound.serve(x)
    y_prog.block_until_ready()                  # warm the program path
    y_leg = legacy_call()
    y_leg.block_until_ready()                   # warm the legacy jit cache
    match = bool(jnp.all(y_prog == y_leg))

    t0 = time.time()
    for _ in range(steps):
        legacy_call().block_until_ready()
    t_leg = (time.time() - t0) / steps

    t0 = time.time()
    for _ in range(steps):
        bound.serve(x).block_until_ready()
    t_prog = (time.time() - t0) / steps

    row = {
        "batch": batch, "d_model": d, "layers": layers, "steps": steps,
        "legacy_us_per_call": t_leg * 1e6,
        "program_us_per_call": t_prog * 1e6,
        "legacy_tokens_per_s": batch / t_leg,
        "program_tokens_per_s": batch / t_prog,
        "speedup": t_leg / t_prog,
        "match": match,
        "program_stats": prog.stats(),
    }
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(row, fh, indent=2)
    return row


def bench_verify_overhead(d=192, layers=2, batch=8):
    """One-time cost of `compile_program(..., verify="strict")` (ISSUE 8).

    Plans and warms a genuinely cold program at the most expensive grid
    point (r_in=8, r_w=4 — 32 kernel planes), then times the full cimcheck
    pass stack (`verify_program`) against it.  The acceptance gate is
    overhead < 5% of the one-time plan+warmup cost: static verification
    must stay invisible next to the XLA compile it rides along with."""
    from repro.analysis import verify_program
    from repro.core.mapping import LayerSpec
    from repro.runtime import compile_program
    from repro.runtime.program import clear_program_cache

    specs = [LayerSpec(m=batch, k=d, n=d, r_in=8, r_w=4)
             for _ in range(layers)]
    clear_program_cache()
    t0 = time.time()
    prog = compile_program(specs)
    params = prog.init_params(jax.random.PRNGKey(0))
    bound = prog.bind(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, d))
    bound.serve(x).block_until_ready()
    t_plan = time.time() - t0

    t0 = time.time()
    verify_program(prog, "strict", graphs="serving")   # = verify="strict"
    t_verify = time.time() - t0
    return {
        "plan_warmup_s": t_plan,
        "verify_s": t_verify,
        "verify_strict_overhead": t_verify / t_plan,
    }


def bench_inflight_sweep(rates=(0.25, 1.0, 4.0), capacity=8, n_req=16,
                         seed=0):
    """Arrival-rate sweep of the in-flight batching scheduler (ISSUE 6).

    Poisson arrivals (requests per scheduler step, one stream per rate) x
    a short/medium/long generation-length mix, driven through
    InflightScheduler over a toy CIMDecodeLM.  Per rate: p50/p99 end-to-
    end latency and time-to-first-token (steps), decode tokens/s, mean
    fused occupancy, and an isolation spot-check — a sample of requests
    re-decoded solo (decode_sequential) must match the fused streams bit
    for bit."""
    from repro.runtime.scheduler import (CIMDecodeLM, InflightScheduler,
                                         Request, decode_sequential)

    model = CIMDecodeLM.toy(jax.random.PRNGKey(5), d=96, depth=2,
                            vocab=61, r_in=4, r_w=2)
    gen_mix = ((2, 0.5), (6, 0.3), (12, 0.2))     # short/medium/long
    rows = []
    for rate in rates:
        rng = np.random.default_rng(seed)
        t, arrivals = 0.0, []
        for uid in range(n_req):
            t += rng.exponential(1.0 / rate)
            gen = int(rng.choice([g for g, _ in gen_mix],
                                 p=[p for _, p in gen_mix]))
            prompt = tuple(int(v) for v in
                           rng.integers(0, 61, size=int(rng.integers(1, 5))))
            arrivals.append((int(t), Request(uid=uid, prompt=prompt,
                                             max_new_tokens=gen)))
        sched = InflightScheduler(model, capacity=capacity)
        fused = sched.run(arrivals)
        m = sched.metrics()
        sample = [r for _, r in arrivals[:: max(1, n_req // 3)]]
        match = all(fused[r.uid] == decode_sequential(model, r)
                    for r in sample)
        rows.append({
            "arrival_rate": rate, "requests": n_req, "capacity": capacity,
            "latency_steps_p50": m["latency_steps_p50"],
            "latency_steps_p99": m["latency_steps_p99"],
            "ttft_steps_p50": m["ttft_steps_p50"],
            "ttft_steps_p99": m["ttft_steps_p99"],
            "tokens_per_s": m["tokens_per_s"],
            "tokens_per_decode_step": m["tokens_per_decode_step"],
            "extents_seen": m["extents_seen"],
            "isolation_match": match,
        })
    return rows


def bench_llm_engine(steps=8):
    """Engine-mode LLM projections (ISSUE 7): per-expert program-cache
    reuse and engine-vs-fakequant throughput on a small MoE block.

    One moe_block forward routes 3E expert GEMMs (gate/up/down x E
    experts) through TWO cached programs — the (d->f) program shared by
    the gate and up banks and the (f->d) down program — so the program
    cache absorbs (3E-2)/3E of the compiles.  The row reports that hit
    rate, the per-program serve reuse factor, tokens/s for the engine vs
    the fakequant reference, and their bit-exactness."""
    import functools

    from repro.core import mapping
    from repro.core.cim_layers import CIMConfig, _engine_config
    from repro.models.moe import init_moe, moe_block
    from repro.runtime.program import DEFAULT_BUCKETS, compile_program

    e, d, f, top_k, cf = 4, 32, 96, 2, 1.25
    params = init_moe(jax.random.PRNGKey(0), d, f, e)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, d), jnp.float32)
    cim_fq = CIMConfig(mode="fakequant", r_in=4, r_w=2)
    cim_en = cim_fq.replace(mode="engine")
    run_fq = jax.jit(functools.partial(moe_block, n_experts=e, top_k=top_k,
                                       capacity_factor=cf, cim=cim_fq))
    run_en = jax.jit(functools.partial(moe_block, n_experts=e, top_k=top_k,
                                       capacity_factor=cf, cim=cim_en))

    # replicate the capacity -> bucket -> LayerSpec key moe_block uses so
    # the stats below read the very programs its expert loop serves
    t = x.shape[0] * x.shape[1]
    cap = max(8, min(int(cf * top_k * t / e + 0.5), t * top_k))
    m = DEFAULT_BUCKETS.bucket_for(cap)
    progs = [compile_program(
        [mapping.LayerSpec(m=m, k=ki, n=ni, r_in=cim_en.r_in,
                           r_w=cim_en.r_w, r_out=cim_en.r_out)],
        _engine_config(cim_en)) for ki, ni in ((d, f), (f, d))]
    serves0 = sum(p.stats()["serve_calls"] for p in progs)

    y_en, _ = run_en(params, x)
    y_en.block_until_ready()
    y_fq, _ = run_fq(params, x)
    y_fq.block_until_ready()
    match = bool(jnp.all(y_en == y_fq))
    serves = sum(p.stats()["serve_calls"] for p in progs) - serves0

    times = {}
    for name, fn in (("engine", run_en), ("fakequant", run_fq)):
        t0 = time.time()
        for _ in range(steps):
            fn(params, x)[0].block_until_ready()
        times[name] = (time.time() - t0) / steps
    return {
        "n_experts": e, "d_model": d, "d_ff": f, "top_k": top_k,
        "tokens_per_call": t,
        "expert_gemm_serves": serves,
        "programs_compiled": len(progs),
        "program_cache_hit_rate": 1.0 - len(progs) / max(serves, 1),
        "serve_reuse_factor": serves / len(progs),
        "engine_tokens_per_s": t / times["engine"],
        "fakequant_tokens_per_s": t / times["fakequant"],
        "engine_us_per_call": times["engine"] * 1e6,
        "fakequant_us_per_call": times["fakequant"] * 1e6,
        "match": match,
    }


def bench_autotune(devices=(1, 4)):
    """Schedule-autotuner gate (ISSUE 9): tuned cost <= heuristic cost on
    every zoo model x precision point, and tuned programs bit-exact.

    The cost sweep is pure plan-time geometry (repro.tuner.tune_layer on
    the LeNet conv chain and the olmo-1b projection GEMMs across the full
    r_in x r_w grid, at 1 and 4 modeled devices — no fake-device mesh
    needed, the roofline model only reads the partition arithmetic).  One
    compiled point then checks the integrated path: a
    compile_program(tune="analytic") program must serve bit-identically
    to the untuned one."""
    from repro.configs import get_smoke_config
    from repro.core.cim_layers import CIMConfig, _engine_config
    from repro.core.mapping import LayerSpec
    from repro.models.cnn import lenet_engine_specs
    from repro.runtime.engine import EngineConfig
    from repro.runtime.program import compile_program
    from repro.tuner import SEARCH_COUNT, tune_layer

    def llm_specs(arch, r_in, r_w, m=8):
        # the decoder projection GEMMs, same shapes scripts/cimcheck.py
        # sweeps (fused QKV, O, fused gate_up, down)
        c = get_smoke_config(arch)
        hd = c.resolved_head_dim
        shapes = [(c.d_model, (c.n_heads + 2 * c.n_kv_heads) * hd),
                  (c.n_heads * hd, c.d_model),
                  (c.d_model, 2 * c.d_ff), (c.d_ff, c.d_model)]
        return [LayerSpec(m=m, k=k, n=n, r_in=r_in, r_w=r_w)
                for k, n in shapes]

    points = 0
    wins = 0
    ratio_sum = 0.0
    all_le = True
    n0 = SEARCH_COUNT["n"]
    for r_in, r_w in PRECISIONS:
        zoo = []
        specs, _, _ = lenet_engine_specs(
            8, cim=CIMConfig(r_in=r_in, r_w=r_w))
        zoo.append(("lenet", specs, _engine_config(
            CIMConfig(r_in=r_in, r_w=r_w))))
        zoo.append(("olmo-1b", llm_specs("olmo-1b", r_in, r_w),
                    EngineConfig()))
        for _, specs, cfg in zoo:
            for d in devices:
                heur_s = tuned_s = 0.0
                for spec in specs:
                    _, rep = tune_layer(spec, cfg, d, cache=None)
                    heur_s += rep["heuristic_s"]
                    tuned_s += rep["predicted_s"]
                points += 1
                all_le &= tuned_s <= heur_s * (1 + 1e-12)
                wins += tuned_s < heur_s
                ratio_sum += tuned_s / max(heur_s, 1e-30)

    spec = [LayerSpec(m=16, k=300, n=48, r_in=4, r_w=2)]
    p0 = compile_program(spec, EngineConfig())
    pt = compile_program(spec, EngineConfig(), tune="analytic",
                         tune_cache="")
    params = p0.init_params(jax.random.PRNGKey(0))
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(1), (6, 300)))
    y0 = p0.bind(params).serve(x)
    yt = pt.bind(params).serve(x)
    match = bool(jnp.all(y0 == yt))
    return {
        "zoo_points": points,
        "layers_searched": SEARCH_COUNT["n"] - n0,
        "tuned_le_heuristic": bool(all_le),
        "points_improved": int(wins),
        "mean_cost_ratio": ratio_sum / max(points, 1),
        "match": match,
    }


def bench_precision_serving(capacity=6, n_req=12, steps=12, seed=3):
    """Workload-adaptive precision serving gate (ISSUE 10 tentpole).

    Calibrates the toy decode-LM's four projection GEMMs, plans quality
    and throughput operating points under DEFAULT_BUDGETS, builds ONE
    CIMDecodeLM serving both points over the same weights, and gates:

      * throughput win — the throughput point's projected decode
        tokens/s (macro perf model over its block stack) beats the
        quality point's.  The projection is the gate because interpret-
        mode CPU wall-clock cannot resolve the bit-plane difference (the
        plane loop fuses into one XLA op; dispatch overhead dominates) —
        measured wall tokens/s for both points is still reported for
        trend tracking;
      * mixed bit-exactness — a half/half schedule where every fused
        request must equal its solo decode at its own point;
      * budget adherence — a fresh sensitivity profile (different seed,
        different input draws) re-measures each point's total quality
        delta, which must stay within the planner's allowance/prediction
        up to a bounded slack.
    """
    from repro.core.mapping import LayerSpec
    from repro.precision import DEFAULT_BUDGETS, assign, calibrate
    from repro.runtime.engine import EngineConfig
    from repro.runtime.scheduler import (CIMDecodeLM, InflightScheduler,
                                         Request, decode_sequential)

    d, d_ff, depth, vocab = 48, 96, 2, 23
    specs = (LayerSpec(m=8, k=d, n=3 * d, r_in=8, r_w=4),
             LayerSpec(m=8, k=d, n=d, r_in=8, r_w=4),
             LayerSpec(m=8, k=d, n=2 * d_ff, r_in=8, r_w=4),
             LayerSpec(m=8, k=d_ff, n=d, r_in=8, r_w=4))
    cfg = EngineConfig()
    prof = calibrate(specs, cfg, n_trials=2, batch=4, seed=seed,
                     label="bench-precision")
    points = {}
    predicted = {}
    allowance = {}
    for name in ("quality", "throughput"):
        asg, delta = assign(prof, specs, DEFAULT_BUDGETS[name])
        points[name] = asg
        predicted[name] = delta
        allowance[name] = DEFAULT_BUDGETS[name] * prof.max_total_delta()

    model = CIMDecodeLM.toy(jax.random.PRNGKey(11), d=d, depth=depth,
                            vocab=vocab, r_in=8, r_w=4, points=points)

    rng = np.random.default_rng(seed)
    prompts = [tuple(int(v) for v in rng.integers(0, vocab, size=3))
               for _ in range(n_req)]
    gens = [int(rng.integers(2, 5)) for _ in range(n_req)]

    def run_uniform(point):
        sched = InflightScheduler(model, capacity=capacity)
        sched.run([(i % 3, Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=gens[i], point=point))
                   for i in range(n_req)])
        return sched.metrics()

    # warm both points' executables, then measure (same schedule per point)
    for name in points:
        run_uniform(name)
    m_q = run_uniform("quality")
    m_t = run_uniform("throughput")

    def point_step_time(point):
        # modeled macro time of ONE fused decode step at this point: the
        # four projection programs of every block (Fig. 22 scaling)
        t = 0.0
        for blk in model.blocks_for(point):
            for bp in (blk.qkv, blk.o, blk.gate_up, blk.down):
                t += bp.program.perf_report(
                    point=point)["total"]["time_s"]
        return t

    t_q, t_t = point_step_time("quality"), point_step_time("throughput")
    projected = {"quality": capacity / max(t_q, 1e-30),
                 "throughput": capacity / max(t_t, 1e-30)}
    speedup = projected["throughput"] / max(projected["quality"], 1e-30)

    mixed = [Request(uid=i, prompt=prompts[i], max_new_tokens=gens[i],
                     point=("quality", "throughput")[i % 2])
             for i in range(n_req)]
    sched = InflightScheduler(model, capacity=capacity)
    fused = sched.run([(i % 3, r) for i, r in enumerate(mixed)])
    mixed_match = all(fused[r.uid] == decode_sequential(model, r)
                      for r in mixed)

    # MC budget check: fresh input draws re-measure the deltas the
    # planner summed — 2.5x slack bounds the draw-to-draw variation
    prof2 = calibrate(specs, cfg, n_trials=2, batch=4, seed=seed + 1,
                      label="bench-precision-check")
    within_budget = True
    measured = {}
    for name, asg in points.items():
        meas = sum(prof2.delta(i, pt) for i, pt in enumerate(asg))
        measured[name] = meas
        within_budget &= meas <= max(allowance[name],
                                     predicted[name]) * 2.5 + 1e-12
    return {
        "capacity": capacity, "requests": n_req,
        "points": {k: [list(p) for p in v] for k, v in points.items()},
        "predicted_delta": predicted,
        "allowance": allowance,
        "measured_delta": measured,
        "quality_tokens_per_s": projected["quality"],
        "throughput_tokens_per_s": projected["throughput"],
        "quality_wall_tokens_per_s": m_q["tokens_per_s"],
        "throughput_wall_tokens_per_s": m_t["tokens_per_s"],
        "speedup": speedup,
        "mixed_tokens_by_point": sched.metrics()["tokens_by_point"],
        "mixed_match": mixed_match,
        "within_budget": within_budget,
    }


def _serving_row(out_json="BENCH_serving.json"):
    """Run bench_serving plus the in-flight arrival-rate sweep, merge both
    into one BENCH_serving.json, print the CSV rows, and return whether
    every bit-exactness check (program-vs-legacy and fused-vs-solo
    isolation) held."""
    import json

    row = bench_serving(out_json=None)
    print(f"serving_program,{row['program_us_per_call']:.0f},"
          f"legacy{row['legacy_us_per_call']:.0f}us_"
          f"speedup{row['speedup']:.2f}_match{row['match']}")
    sweep = bench_inflight_sweep()
    for r in sweep:
        print(f"serving_inflight_rate{r['arrival_rate']:g},"
              f"{r['tokens_per_s']:.0f},"
              f"p50_{r['latency_steps_p50']:.0f}_"
              f"p99_{r['latency_steps_p99']:.0f}steps_"
              f"occ{r['tokens_per_decode_step']:.2f}_"
              f"match{r['isolation_match']}")
    row["inflight_sweep"] = sweep
    llm = bench_llm_engine()
    print(f"serving_llm_engine,{llm['engine_tokens_per_s']:.0f},"
          f"fakequant{llm['fakequant_tokens_per_s']:.0f}tok_s_"
          f"hit{llm['program_cache_hit_rate']:.2f}_"
          f"reuse{llm['serve_reuse_factor']:.1f}x_match{llm['match']}")
    row["llm_engine"] = llm
    at = bench_autotune()
    print(f"serving_autotune,{at['zoo_points']},"
          f"ratio{at['mean_cost_ratio']:.3f}_"
          f"improved{at['points_improved']}_"
          f"le{at['tuned_le_heuristic']}_match{at['match']}")
    row["autotune"] = at
    vo = bench_verify_overhead()
    print(f"serving_verify_strict,{vo['verify_s'] * 1e3:.0f}ms,"
          f"plan{vo['plan_warmup_s'] * 1e3:.0f}ms_"
          f"overhead{vo['verify_strict_overhead']:.3f}")
    row.update(vo)
    ps = bench_precision_serving()
    print(f"serving_precision_sweep,"
          f"{ps['throughput_tokens_per_s']:.0f},"
          f"quality{ps['quality_tokens_per_s']:.0f}tok_s_"
          f"speedup{ps['speedup']:.2f}_"
          f"mixed{ps['mixed_match']}_budget{ps['within_budget']}")
    row["precision_sweep"] = ps
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(row, fh, indent=2)
    return (row["match"] and llm["match"]
            and at["match"] and at["tuned_le_heuristic"]
            and all(r["isolation_match"] for r in sweep)
            and ps["mixed_match"] and ps["within_budget"]
            and ps["speedup"] > 1.0)


def main(serving_only=False):
    ok = True
    if serving_only:
        if not _serving_row():
            raise SystemExit("program vs legacy serving mismatch")
        return
    for (m, k, n) in ((128, 1152, 64), (256, 1152, 256), (512, 512, 128)):
        us, match = bench(m, k, n)
        ok &= match
        print(f"kernel_cim_mbiw_{m}x{k}x{n},{us:.0f},match{match}")
    for r_in, r_w, planes, us, gops, match in bench_precision_sweep():
        ok &= match
        print(f"kernel_prec_rin{r_in}_rw{r_w},{us:.0f},"
              f"{gops:.1f}GOPS_planes{planes}_match{match}")
    for r_in, r_w, us, gops, match in bench_conv_sweep():
        ok &= match
        print(f"conv_engine_rin{r_in}_rw{r_w},{us:.0f},"
              f"{gops:.1f}GOPS_match{match}")
    for scale, us, acc, det in bench_noise_sweep():
        ok &= det
        print(f"noise_engine_x{scale:g},{us:.0f},"
              f"acc{acc:.2f}_deterministic{det}")
    devs = jax.devices()
    print(f"shard_engine_devices,0,{devs[0].platform}x{len(devs)}")
    t_serial, srows = bench_scaling_sweep()
    print(f"shard_engine_serial,{t_serial:.0f}")
    for d, t_strong, t_weak, eff, match in srows:
        if t_strong is None:
            print(f"shard_engine_d{d},skipped_needs_{d}_devices")
            continue
        ok &= match
        print(f"shard_engine_d{d},{t_strong:.0f},"
              f"strong_x{t_serial / t_strong:.2f}_weak{t_weak:.0f}us_"
              f"eff{eff:.2f}_match{match}")
    ok &= _serving_row()
    if not ok:
        raise SystemExit("oracle/determinism mismatch in sweep (see log)")


if __name__ == "__main__":
    import sys
    main(serving_only="serving" in sys.argv[1:])
