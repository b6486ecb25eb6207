"""Schedule autotuner acceptance suite (ISSUE 9).

Four contracts, in order of importance:

1. **Tuning never changes numerics.**  Any legal (bm, bn, bk) block
   triple and any legal shard kind produce outputs bit-identical to the
   heuristic schedule — fuzzed over shapes and the full r_in x r_w
   precision grid, clean AND under a fixed noise key, on 1 device and
   (when the mesh allows — the autotune-smoke CI job runs with 4 fake
   CPU devices) on 4.
2. **The cost model is sane.**  Monotone in M/N/K, macro-eval counts
   agree EXACTLY with perfmodel.macro_perf's layer_report, and its
   ranking of pinned shapes matches measured kernel wall-clock with
   Spearman >= 0.7.
3. **The cache degrades, never crashes.**  Corrupt / stale-schema /
   invalid-entry cache files fall back to the heuristic schedule with a
   TuneCacheWarning; a valid hit skips the search entirely
   (SEARCH_COUNT observable).
4. **One hardware table.**  EFFECTIVE_LINKS and the TPU-v5e peaks live
   in core/hw.py and are the very objects benchmarks/roofline.py and
   repro.tuner consume (values pinned by regression).

Multi-device cases skip under the plain tier-1 run (1 device):
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest tests/test_tuner.py
"""
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                  # pragma: no cover
    from hypofallback import given, settings, st

from repro.core import mapping
from repro.core.hw import DEFAULT_MACRO, EFFECTIVE_LINKS, TPU_V5E
from repro.core.mapping import LayerSpec
from repro.core.noise_model import NoiseConfig
from repro.kernels.cim_mbiw import ops
from repro.perfmodel.macro_perf import AcceleratorPerfModel, schedule_report
from repro.runtime import engine as rt
from repro.runtime.engine import EngineConfig, ShardingConfig
from repro.runtime.program import (clear_program_cache, compile_program,
                                   program_for_plan)
from repro.tuner import (SCHEMA_VERSION, ScheduleChoice, TuneCache,
                         TuneCacheWarning, cache_key, heuristic_choice,
                         layer_candidates, layer_cost, tune_layer,
                         tune_network)
from repro.tuner import search as tsearch

N_DEV = len(jax.devices())
R_INS = (1, 2, 4, 8)
R_WS = (1, 2, 4)
NOISE = NoiseConfig(enabled=True)


@pytest.fixture(scope="module", autouse=True)
def _fresh_caches():
    """Start (and leave) this module with empty program/jit caches.

    The suite compiles many one-off kernel variants (fuzzed block sizes x
    the precision grid).  Stacked on top of the executables the ~400
    earlier tier-1 tests leave in the process-wide caches, that pushes
    XLA's CPU JIT past its limits (observed SIGSEGV in backend_compile
    when this file runs last in the full suite, while the same tests pass
    standalone).  Dropping the caches at both boundaries keeps the
    process's compiled-code footprint bounded without changing any test's
    semantics — everything here re-plans/re-compiles what it needs.
    """
    clear_program_cache()
    jax.clear_caches()
    yield
    clear_program_cache()
    jax.clear_caches()


def _need(devices):
    if N_DEV < devices:
        pytest.skip(f"needs {devices} devices, jax reports {N_DEV} (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _run_pair(spec, cfg, schedule, *, noisy=False, seed=0):
    """(heuristic output, overridden-schedule output) of one layer."""
    p0 = rt.plan_network((spec,), cfg)
    pt = rt.plan_network((spec,), cfg, schedule=(schedule,))
    params = rt.init_network_params(p0, jax.random.PRNGKey(seed))
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                      (spec.m, spec.k)))
    key = jax.random.PRNGKey(7) if noisy else None
    y0 = program_for_plan(p0).run(params, x, key=key)
    yt = program_for_plan(pt).run(params, x, key=key)
    return np.asarray(y0), np.asarray(yt)


# ---------------------------------------------------------------------------
# 1. bit-exactness: tuned schedules never move a bit
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(1, 20), st.integers(8, 320), st.integers(4, 64),
       st.sampled_from([(r_in, r_w) for r_in in R_INS for r_w in R_WS]),
       st.sampled_from(ops.BM_PALETTE), st.sampled_from(ops.BN_PALETTE),
       st.sampled_from(ops.BK_PALETTE))
def test_fuzz_blocks_bitexact(m, k, n, prec, bm, bn, bk):
    """Any palette block triple is bit-exact with the heuristic blocks,
    fuzzed over shapes and precision (clean run)."""
    r_in, r_w = prec
    spec = LayerSpec(m=m, k=k, n=n, r_in=r_in, r_w=r_w)
    y0, yt = _run_pair(spec, EngineConfig(), ((bm, bn, bk), None))
    assert (y0 == yt).all()


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 16), st.integers(8, 256), st.integers(4, 48),
       st.sampled_from([(1, 1), (4, 2), (8, 4)]),
       st.sampled_from(ops.BM_PALETTE), st.sampled_from(ops.BK_PALETTE))
def test_fuzz_blocks_bitexact_noise(m, k, n, prec, bm, bk):
    """Block overrides stay bit-exact under a fixed noise key: the
    thermal draws are keyed per global row block, not per kernel block."""
    r_in, r_w = prec
    spec = LayerSpec(m=m, k=k, n=n, r_in=r_in, r_w=r_w)
    cfg = EngineConfig(noise=NOISE)
    y0, yt = _run_pair(spec, cfg, ((bm, 64, bk), None), noisy=True)
    assert (y0 == yt).all()


@pytest.mark.parametrize("r_in", R_INS)
@pytest.mark.parametrize("r_w", R_WS)
def test_grid_bitexact(r_in, r_w):
    """The full precision grid at a deliberately off-heuristic block
    choice (small bm/bn, padded bk) — bit-exact everywhere."""
    spec = LayerSpec(m=12, k=200, n=40, r_in=r_in, r_w=r_w)
    y0, yt = _run_pair(spec, EngineConfig(), ((32, 32, 1024), None))
    assert (y0 == yt).all()


@pytest.mark.parametrize("kind", ["col", "rows"])
@pytest.mark.parametrize("noisy", [False, True])
def test_sharded_kind_override_bitexact(kind, noisy):
    """Forcing either shard kind (plus a block override) on a 4-device
    mesh is bit-exact with the auto-kind heuristic plan, clean and under
    a fixed noise key."""
    _need(4)
    spec = LayerSpec(m=16, k=300, n=320, r_in=4, r_w=2)   # 5 col tiles
    cfg = EngineConfig(sharding=ShardingConfig(devices=4),
                       noise=NOISE if noisy else rt.NO_NOISE)
    y0, yt = _run_pair(spec, cfg, ((64, 64, 128), kind), noisy=noisy)
    assert (y0 == yt).all()


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 16), st.integers(16, 256), st.integers(8, 300),
       st.sampled_from([(2, 1), (4, 2), (8, 4)]),
       st.sampled_from(["col", "rows"]))
def test_fuzz_sharded_bitexact(m, k, n, prec, kind):
    """Fuzzed shapes x precision x forced shard kind on 4 devices: every
    legal partition is bit-exact with the heuristic plan."""
    _need(4)
    r_in, r_w = prec
    spec = LayerSpec(m=m, k=k, n=n, r_in=r_in, r_w=r_w)
    cfg = EngineConfig(sharding=ShardingConfig(devices=4))
    y0, yt = _run_pair(spec, cfg, (None, kind))
    assert (y0 == yt).all()


def test_compile_program_tune_bitexact():
    """compile_program(tune=...) end to end: analytic and measure tuned
    programs serve bit-identically to tune="off", and the tuned plan's
    schedule_report echoes the chosen blocks and predicted cost."""
    clear_program_cache()
    specs = (LayerSpec(m=16, k=300, n=40, r_in=4, r_w=2),)
    # a config whose preferred bk (256) is no whole row tile, so the
    # heuristic pads K and the search has a win to find
    cfg = EngineConfig(bk=256)
    p0 = compile_program(specs, cfg)
    pa = compile_program(specs, cfg, tune="analytic", tune_cache="")
    pm = compile_program(specs, cfg, tune="measure", tune_cache="")
    params = p0.init_params(jax.random.PRNGKey(0))
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(1), (5, 300)))
    y0 = np.asarray(p0.bind(params).serve(x))
    assert (y0 == np.asarray(pa.bind(params).serve(x))).all()
    assert (y0 == np.asarray(pm.bind(params).serve(x))).all()
    # k=300 at bk=256 pads K to 512; the palette's clamped bk=304 pads to
    # 304 — a strictly-lower-DMA win the tuner must find and the report
    # must echo
    assert pa.plan.layers[0].blocks is not None
    rep = schedule_report(pa.plan)["layers"][0]["tune"]
    assert rep["blocks"] == pa.plan.layers[0].blocks
    assert rep["predicted_s"] <= rep["heuristic_s"]
    with pytest.raises(ValueError, match="tune"):
        compile_program(specs, EngineConfig(), tune="nope")


def test_tuned_no_win_folds_to_heuristic_plan():
    """A layer whose search keeps the heuristic produces the *same* plan
    (hash-equal), so the tuned program shares the untuned executables."""
    spec = LayerSpec(m=8, k=128, n=32, r_in=4, r_w=2)
    cfg = EngineConfig()
    heur = heuristic_choice(spec, cfg)
    best, rep = tune_layer(spec, cfg, 1, cache=None)
    if best != heur:
        pytest.skip("tuner found a genuine win on this shape")
    plan_t, _ = tune_network([spec], cfg, cache_path="")
    assert plan_t == rt.plan_network((spec,), cfg)
    assert hash(plan_t) == hash(rt.plan_network((spec,), cfg))


def test_schedule_override_validation():
    """Bad overrides fail loudly at plan time, not at dispatch."""
    spec = LayerSpec(m=8, k=64, n=16, r_in=4, r_w=2)
    with pytest.raises(ValueError, match="blocks"):
        rt.plan_layer(spec, blocks=(0, 64, 64))
    with pytest.raises(ValueError, match="sharding"):
        rt.plan_layer(spec, shard_kind="col")
    with pytest.raises(ValueError, match="kind"):
        mapping.shard_layer(spec, mapping.map_layer(spec, DEFAULT_MACRO),
                            2, kind="diagonal")
    with pytest.raises(ValueError, match="schedule"):
        rt.plan_network((spec,), EngineConfig(),
                        schedule=(None, ((1, 1, 1), None)))
    with pytest.raises(ValueError, match="mode"):
        tune_network([spec], EngineConfig(), mode="psychic")


# ---------------------------------------------------------------------------
# 2. cost-model sanity
# ---------------------------------------------------------------------------

def test_cost_macro_evals_agree_with_macro_perf():
    """The cost model's eval counts equal macro_perf's layer_report
    EXACTLY across the precision grid and assorted geometries."""
    ap = AcceleratorPerfModel()
    shapes = [(8, 64, 16), (16, 300, 40), (4, 1300, 256), (32, 2048, 512)]
    for r_in in R_INS:
        for r_w in R_WS:
            for m, k, n in shapes:
                spec = LayerSpec(m=m, k=k, n=n, r_in=r_in, r_w=r_w)
                lc = layer_cost(spec, heuristic_choice(spec, EngineConfig()))
                assert lc.macro_evals == \
                    ap.layer_report(spec)["macro_evals"]
                assert lc.macro_evals_per_device == lc.macro_evals


def test_cost_sharded_evals_match_schedule_report():
    """Per-device eval counts of both shard kinds equal the counts
    schedule_report derives from the planned LayerShard."""
    spec = LayerSpec(m=16, k=300, n=320, r_in=4, r_w=2)   # 5 col tiles
    cfg = EngineConfig(sharding=ShardingConfig(devices=4))
    for kind in ("col", "rows"):
        plan = rt.plan_network((spec,), cfg, schedule=((None, kind),))
        rep = schedule_report(plan)["layers"][0]["shard"]
        lc = layer_cost(spec, ScheduleChoice(64, 64, 256, kind), devices=4)
        assert lc.macro_evals_per_device == rep["macro_evals_per_device"]


def test_cost_monotone_in_mnk():
    """Doubling any one GEMM dimension never lowers the modeled cost or
    the DMA traffic (the roofline terms are all non-decreasing)."""
    choice = ScheduleChoice(64, 64, 256)
    base = dict(m=8, k=128, n=32)
    for dim in ("m", "k", "n"):
        prev = None
        for mult in (1, 2, 4, 8):
            kw = dict(base)
            kw[dim] = base[dim] * mult
            lc = layer_cost(LayerSpec(r_in=4, r_w=2, **kw), choice)
            if prev is not None:
                assert lc.total_s >= prev.total_s, dim
                assert lc.dma_bytes >= prev.dma_bytes, dim
                assert lc.macro_evals >= prev.macro_evals, dim
            prev = lc


def _spearman(a, b):
    """Rank correlation, hand-rolled (scipy is not a dependency)."""
    def rank(v):
        order = sorted(range(len(v)), key=lambda i: v[i])
        r = [0] * len(v)
        for pos, i in enumerate(order):
            r[i] = pos
        return r
    ra, rb = rank(a), rank(b)
    n = len(a)
    d2 = sum((x - y) ** 2 for x, y in zip(ra, rb))
    return 1.0 - 6.0 * d2 / (n * (n * n - 1))


def test_cost_spearman_vs_measured():
    """The analytic ranking of pinned shapes agrees with measured kernel
    wall-clock at Spearman >= 0.7.  Interpret mode on CPU has a ~20ms
    per-dispatch floor, so the pinned shapes all sit well above it
    (>= ~9M MACs) with >= ~2x work ratios between neighbors; every shape
    is compiled before any is timed (min of 3)."""
    shapes = [(64, 1152, 128), (96, 1152, 256), (128, 1152, 512),
              (256, 1152, 512), (512, 1152, 1024)]
    predicted, cases = [], []
    for m, k, n in shapes:
        spec = LayerSpec(m=m, k=k, n=n, r_in=4, r_w=2)
        predicted.append(
            layer_cost(spec, heuristic_choice(spec, EngineConfig())).total_s)
        rng = np.random.default_rng(m + k)
        x = jax.numpy.asarray(rng.integers(0, 16, (m, k), dtype=np.int32))
        w = jax.numpy.asarray(
            2 * rng.integers(0, 2, (k, n), dtype=np.int32) + 1)
        gamma = jax.numpy.full((n,), 16.0)
        beta = jax.numpy.zeros((n,))

        def run(x=x, w=w, gamma=gamma, beta=beta):
            ops.cim_matmul(x, w, gamma, beta, r_in=4, r_out=8,
                           g0=1.0).block_until_ready()
        run()                                   # compile before timing
        cases.append(run)
    measured = []
    for run in cases:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            run()
            best = min(best, time.perf_counter() - t0)
        measured.append(best)
    rho = _spearman(predicted, measured)
    assert rho >= 0.7, (rho, predicted, measured)


def test_candidates_heuristic_first_and_legal():
    """layer_candidates puts the heuristic first, deduplicates, and every
    candidate's blocks are positive and tile-clamped."""
    spec = LayerSpec(m=16, k=1300, n=320, r_in=4, r_w=2)
    cfg = EngineConfig()
    cands = layer_candidates(spec, cfg, 1)
    assert cands[0] == heuristic_choice(spec, cfg)
    assert len(set(cands)) == len(cands)
    mp = mapping.map_layer(spec, DEFAULT_MACRO)
    tile_n = -(-spec.n // mp.col_tiles)
    for c in cands:
        assert c.bm >= 1 and c.bn >= 1 and c.bk >= 1
        assert c.bm <= -(-spec.m // 8) * 8
        assert c.bn <= -(-tile_n // 8) * 8
        assert c.bk <= -(-mp.rows_per_tile // 8) * 8
        assert c.shard_kind is None
    # multi-device candidates carry both kinds
    kinds = {c.shard_kind for c in layer_candidates(spec, cfg, 4)}
    assert kinds == {None, "col", "rows"}


def test_search_never_worse_than_heuristic():
    """tune_layer's winner scores <= the heuristic on every zoo-ish
    geometry x precision x device point (the BENCH gate in miniature)."""
    shapes = [(8, 64, 16), (16, 300, 40), (8, 1300, 256), (32, 576, 320)]
    for r_in, r_w in ((1, 1), (4, 2), (8, 4)):
        for m, k, n in shapes:
            spec = LayerSpec(m=m, k=k, n=n, r_in=r_in, r_w=r_w)
            for d in (1, 4):
                _, rep = tune_layer(spec, EngineConfig(), d, cache=None)
                assert rep["predicted_s"] <= rep["heuristic_s"] * (1 + 1e-12)


# ---------------------------------------------------------------------------
# 3. cache round-trip and degradation
# ---------------------------------------------------------------------------

def _count():
    return tsearch.SEARCH_COUNT["n"]


def test_cache_roundtrip_hit_skips_search(tmp_path):
    """Miss -> search + write-back; second compile with the same cache is
    all hits and runs zero searches; the winner is identical."""
    path = str(tmp_path / "tune.json")
    specs = [LayerSpec(m=16, k=300, n=40, r_in=4, r_w=2),
             LayerSpec(m=16, k=40, n=24, r_in=4, r_w=2)]
    cfg = EngineConfig()
    n0 = _count()
    plan1, reps1 = tune_network(specs, cfg, cache_path=path)
    assert _count() - n0 == len(specs)
    assert os.path.exists(path)
    assert all(r["cache"] == "miss" for r in reps1)
    n1 = _count()
    plan2, reps2 = tune_network(specs, cfg, cache_path=path)
    assert _count() == n1                      # hits skip the search
    assert all(r["cache"] == "hit" for r in reps2)
    assert [r["choice"] for r in reps2] == [r["choice"] for r in reps1]
    assert plan1 == plan2
    with open(path) as fh:
        raw = json.load(fh)
    assert raw["schema"] == SCHEMA_VERSION
    assert cache_key(specs[0], 1) in raw["entries"]


def test_cache_corrupt_falls_back_heuristic(tmp_path):
    """A corrupt cache file warns and yields the heuristic plan — no
    search, no crash, no write-back growing the bad file."""
    path = str(tmp_path / "tune.json")
    with open(path, "w") as fh:
        fh.write("{ this is not json")
    spec = LayerSpec(m=16, k=300, n=40, r_in=4, r_w=2)
    n0 = _count()
    with pytest.warns(TuneCacheWarning, match="unreadable"):
        plan, reps = tune_network([spec], EngineConfig(), cache_path=path)
    assert _count() == n0
    assert reps[0]["cache"] == "invalid"
    assert plan == rt.plan_network((spec,), EngineConfig())
    with open(path) as fh:
        assert fh.read() == "{ this is not json"     # untouched


def test_cache_stale_schema_falls_back_heuristic(tmp_path):
    """A schema-version mismatch degrades exactly like corruption."""
    path = str(tmp_path / "tune.json")
    with open(path, "w") as fh:
        json.dump({"schema": SCHEMA_VERSION + 1, "entries": {}}, fh)
    spec = LayerSpec(m=16, k=300, n=40, r_in=4, r_w=2)
    with pytest.warns(TuneCacheWarning, match="schema"):
        plan, reps = tune_network([spec], EngineConfig(), cache_path=path)
    assert reps[0]["cache"] == "invalid"
    assert plan == rt.plan_network((spec,), EngineConfig())


def test_cache_invalid_entry_falls_back_heuristic(tmp_path):
    """One malformed entry degrades only its own layer (warn +
    heuristic); a valid entry in the same file still hits."""
    path = str(tmp_path / "tune.json")
    s_bad = LayerSpec(m=16, k=300, n=40, r_in=4, r_w=2)
    s_good = LayerSpec(m=16, k=40, n=24, r_in=4, r_w=2)
    entries = {
        cache_key(s_bad, 1): {"bm": -5, "bn": "x", "bk": 128,
                              "shard_kind": None},
        cache_key(s_good, 1): {"bm": 8, "bn": 24, "bk": 40,
                               "shard_kind": None},
    }
    with open(path, "w") as fh:
        json.dump({"schema": SCHEMA_VERSION, "entries": entries}, fh)
    n0 = _count()
    with pytest.warns(TuneCacheWarning, match="invalid"):
        plan, reps = tune_network([s_bad, s_good], EngineConfig(),
                                  cache_path=path)
    assert reps[0]["cache"] == "invalid"
    assert reps[1]["cache"] == "hit"
    assert reps[1]["choice"] == ScheduleChoice(8, 24, 40, None)
    assert plan.layers[1].blocks == (8, 24, 40)
    assert _count() == n0                      # neither layer searched


def test_cache_key_discriminates():
    """The key separates geometry, precision, device count and macro
    config — anything a winner depends on."""
    s = LayerSpec(m=8, k=64, n=16, r_in=4, r_w=2)
    base = cache_key(s, 1)
    assert base != cache_key(LayerSpec(m=8, k=64, n=32, r_in=4, r_w=2), 1)
    assert base != cache_key(LayerSpec(m=8, k=64, n=16, r_in=8, r_w=2), 1)
    assert base != cache_key(s, 4)
    import dataclasses as dc
    small = dc.replace(DEFAULT_MACRO, n_rows=576)
    assert base != cache_key(s, 1, small)


def test_cache_bitexact_through_compile_program(tmp_path):
    """The integrated path with a real cache file: first compile misses
    and tunes, a second process-equivalent compile hits — both serve
    bit-identically to the untuned program."""
    clear_program_cache()
    path = str(tmp_path / "tune.json")
    specs = (LayerSpec(m=16, k=300, n=40, r_in=4, r_w=2),)
    p0 = compile_program(specs, EngineConfig())
    p1 = compile_program(specs, EngineConfig(), tune="analytic",
                         tune_cache=path)
    clear_program_cache()                      # force a re-tune from disk
    p2 = compile_program(specs, EngineConfig(), tune="analytic",
                         tune_cache=path)
    assert p1.plan == p2.plan
    params = p0.init_params(jax.random.PRNGKey(0))
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(1), (4, 300)))
    y0 = np.asarray(p0.bind(params).serve(x))
    assert (y0 == np.asarray(p2.bind(params).serve(x))).all()


# ---------------------------------------------------------------------------
# 4. one hardware table
# ---------------------------------------------------------------------------

def test_hw_constants_pinned():
    """The shared hardware table's values (regression pin after the move
    of EFFECTIVE_LINKS out of benchmarks/roofline.py)."""
    assert EFFECTIVE_LINKS == 3.0
    assert TPU_V5E.peak_bf16_flops == 197e12
    assert TPU_V5E.hbm_bw == 819e9
    assert TPU_V5E.ici_bw_per_link == 50e9


def test_roofline_and_tuner_share_hw_table():
    """benchmarks/roofline.py and repro.tuner.cost import the same
    objects from core/hw — one source of truth, not copied constants."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import benchmarks.roofline as rl
    from repro.core import hw
    from repro.tuner import cost as tc
    assert rl.EFFECTIVE_LINKS is hw.EFFECTIVE_LINKS
    assert rl.TPU_V5E is hw.TPU_V5E
    assert tc.EFFECTIVE_LINKS is hw.EFFECTIVE_LINKS
    assert tc.TPU_V5E is hw.TPU_V5E
