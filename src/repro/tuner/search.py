"""Plan-time schedule search: score every candidate, keep the winner.

`tune_network` is the tuner's entry point (what
`runtime.program.compile_program(tune=...)` calls): for each layer it
enumerates the legal (bm, bn, bk) block triples from
`kernels.cim_mbiw.ops.block_candidates` crossed with the legal shard
kinds, scores each with `cost.layer_cost`, and keeps the strict-best —
the heuristic candidate (the EngineConfig blocks + automatic shard kind)
is scored FIRST, so the tuned schedule's analytic cost is <= the
heuristic's by construction.  In "measure" mode the analytic top-k
candidates are additionally wall-clock timed on synthetic tile data and
the fastest measured one wins.

Winners that exactly match the heuristic fold to `None` in the schedule
handed to `plan_network`, so a no-win layer produces a plan that hashes
(and caches) identically to the untuned one.

`SEARCH_COUNT` counts layers actually searched (cache hits skip it) —
the tuner-side mirror of `engine.PLAN_COUNT`, asserted by
tests/test_tuner.py's cache round-trip.

Tuning is numerics-neutral end to end: block sizes never change bits
(exact int32 accumulation — see `kernel_variant_for_tile`) and both
shard kinds are bit-exact partitions of the same schedule, so the search
is free to chase the roofline without a single output bit moving.
"""
from __future__ import annotations

import math
import time
from typing import List, Optional, Sequence, Tuple

from repro.core import mapping
from repro.core.hw import CIMMacroConfig, DEFAULT_MACRO
from repro.kernels.cim_mbiw import ops as kops
from repro.tuner import cache as tcache
from repro.tuner.cost import LayerCost, ScheduleChoice, layer_cost

# layers searched (cache misses that ran the candidate scan); a cache hit
# or a degraded/invalid cache entry does NOT increment it
SEARCH_COUNT = {"n": 0}

MEASURE_TOP_K = 3       # candidates wall-clock timed in "measure" mode
_MEASURE_ITERS = 3      # timing repeats (min taken)

MODES = ("analytic", "measure")


def heuristic_choice(spec: mapping.LayerSpec, cfg,
                     macro: CIMMacroConfig = DEFAULT_MACRO) -> ScheduleChoice:
    """The schedule the engine would run untuned: the EngineConfig block
    sizes clamped to the layer's dispatched tile geometry, automatic
    shard kind (shard_kind=None)."""
    mp = mapping.map_layer(spec, macro)
    tile_n = math.ceil(spec.n / mp.col_tiles)
    return ScheduleChoice(*kops.fit_blocks(
        _n_planes(spec), spec.m, mp.rows_per_tile, tile_n,
        getattr(cfg, "bm", 128), getattr(cfg, "bn", 128),
        getattr(cfg, "bk", 256)), None)


def _n_planes(spec: mapping.LayerSpec) -> int:
    return kops.KernelPrecision(spec.r_in, spec.r_w, spec.r_out).n_planes


def layer_candidates(spec: mapping.LayerSpec, cfg, devices: int,
                     macro: CIMMacroConfig = DEFAULT_MACRO
                     ) -> List[ScheduleChoice]:
    """Every candidate the search scores for one layer, heuristic first.

    Blocks come from the ops palette clamped to (rows, rows_per_tile,
    tile_n); shard kinds are {None} unsharded and {auto-kind-first
    "col"/"rows"} on multi-device plans.  Deduplicated, order-stable."""
    mp = mapping.map_layer(spec, macro)
    tile_n = math.ceil(spec.n / mp.col_tiles)
    if devices <= 1:
        kinds: Tuple[Optional[str], ...] = (None,)
    else:
        auto = "col" if mp.col_tiles >= devices else "rows"
        kinds = (auto, "rows" if auto == "col" else "col")
    out = [heuristic_choice(spec, cfg, macro)]
    seen = {out[0]}
    for kind in kinds:
        rows_local = spec.m
        if kind == "rows":
            rows_local = mapping.shard_layer(spec, mp, devices,
                                             kind=kind).rows_per_device
        for bm, bn, bk in kops.block_candidates(rows_local, mp.rows_per_tile,
                                                tile_n, _n_planes(spec)):
            c = ScheduleChoice(bm, bn, bk, kind)
            if c not in seen:
                seen.add(c)
                out.append(c)
    return out


def _measure_choice_s(spec: mapping.LayerSpec, choice: ScheduleChoice,
                      macro: CIMMacroConfig) -> float:
    """Wall-clock one candidate: run the real kernel on deterministic
    synthetic data for one (row tile, col tile) dispatch and take the min
    of a few repeats.  Used only for ranking — never for numerics."""
    import numpy as np
    import jax

    mp = mapping.map_layer(spec, macro)
    k_tile = min(spec.k, mp.rows_per_tile)
    tile_n = math.ceil(spec.n / mp.col_tiles)
    rng = np.random.default_rng(0)
    x_q = rng.integers(0, 2 ** spec.r_in, (spec.m, k_tile), dtype=np.int32)
    w_q = 2 * rng.integers(0, 2 ** (spec.r_w - 1), (k_tile, tile_n),
                           dtype=np.int32) + 1
    gamma = np.ones((tile_n,), np.float32)
    beta = np.zeros((tile_n,), np.float32)

    def run():
        out = kops.cim_matmul(
            jax.numpy.asarray(x_q), jax.numpy.asarray(w_q),
            jax.numpy.asarray(gamma), jax.numpy.asarray(beta),
            r_in=spec.r_in, r_out=spec.r_out, g0=1.0,
            bm=choice.bm, bn=choice.bn, bk=choice.bk)
        jax.block_until_ready(out)

    run()                              # compile outside the timed region
    best = float("inf")
    for _ in range(_MEASURE_ITERS):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def tune_layer(spec: mapping.LayerSpec, cfg, devices: int, *,
               mode: str = "analytic",
               cache: Optional[tcache.TuneCache] = None,
               macro: CIMMacroConfig = DEFAULT_MACRO
               ) -> Tuple[ScheduleChoice, dict]:
    """Pick one layer's schedule: cache hit -> stored winner (no search);
    miss -> full candidate scan (SEARCH_COUNT += 1) + write-back;
    invalid/degraded cache entry -> heuristic with the cache's warning.

    Returns (choice, report); the report echoes the cache status, the
    heuristic and tuned analytic costs, and the candidate count."""
    heur = heuristic_choice(spec, cfg, macro)
    heur_cost = layer_cost(spec, heur, devices=devices, macro=macro)
    key = tcache.cache_key(spec, devices, macro)
    report = {"key": key, "mode": mode, "heuristic": heur,
              "heuristic_s": heur_cost.total_s}

    status = tcache.MISS
    if cache is not None:
        status, cached = cache.get(key)
        if status == tcache.HIT:
            c_cost = layer_cost(spec, cached, devices=devices, macro=macro)
            report.update(cache=tcache.HIT, choice=cached,
                          predicted_s=c_cost.total_s, candidates=0)
            return cached, report
        if status == tcache.INVALID:
            report.update(cache=tcache.INVALID, choice=heur,
                          predicted_s=heur_cost.total_s, candidates=0)
            return heur, report

    SEARCH_COUNT["n"] += 1
    cands = layer_candidates(spec, cfg, devices, macro)
    scored = [(layer_cost(spec, c, devices=devices, macro=macro), c)
              for c in cands]
    best_cost, best = scored[0]        # the heuristic — ties keep it
    for lc, c in scored[1:]:
        if lc.score() < best_cost.score():
            best_cost, best = lc, c

    if mode == "measure":
        ranked = sorted(scored, key=lambda sc: sc[0].score())
        top = ranked[:MEASURE_TOP_K]
        timed = [(_measure_choice_s(spec, c, macro), lc, c)
                 for lc, c in top]
        _, best_cost, best = min(timed, key=lambda t: t[0])

    if cache is not None:
        cache.put(key, best, mode=mode, total_s=best_cost.total_s)
    report.update(cache=status, choice=best,
                  predicted_s=best_cost.total_s, candidates=len(cands))
    return best, report


def _fold(choice: ScheduleChoice, heur: ScheduleChoice
          ) -> Optional[Tuple[Tuple[int, int, int], Optional[str]]]:
    """Collapse a no-win choice to None so the tuned plan hashes (and
    program-caches) identically to the heuristic plan."""
    if choice == heur:
        return None
    return (choice.blocks, choice.shard_kind)


def tune_network(specs: Sequence[mapping.LayerSpec], cfg,
                 activations: Optional[Sequence[str]] = None,
                 pools: Optional[Sequence[int]] = None, *,
                 mode: str = "analytic",
                 cache_path: Optional[str] = None):
    """Tune every layer and build the (single PLAN_COUNT) tuned plan.

    Returns (NetworkPlan, reports): the plan comes from one
    `engine.plan_network(..., schedule=...)` call with no-win layers
    folded to None, and `reports` is the per-layer tune_layer echo list
    (consumed by `perfmodel.macro_perf.schedule_report`).  Passing
    cache_path="" disables the persistent cache entirely."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    from repro.runtime import engine  # avoid a module-load cycle

    devices = (cfg.sharding.resolve_devices()
               if getattr(cfg, "sharding", None) is not None else 1)
    macro = getattr(cfg, "macro", DEFAULT_MACRO)

    cache = None
    if cache_path != "":
        path = cache_path or tcache.default_cache_path()
        cache = tcache.TuneCache.load(path)

    schedule, reports = [], []
    wrote = False
    for spec in specs:
        choice, rep = tune_layer(spec, cfg, devices, mode=mode,
                                 cache=cache, macro=macro)
        wrote = wrote or rep.get("cache") == tcache.MISS
        schedule.append(_fold(choice, rep["heuristic"]))
        reports.append(rep)
    if cache is not None and wrote:
        cache.save()

    plan = engine.plan_network(specs, cfg, activations, pools,
                               schedule=tuple(schedule))
    return plan, reports
