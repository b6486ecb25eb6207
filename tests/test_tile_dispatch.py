"""One `cim_mbiw` dispatch per row tile.

Col tiles are the macro's unit of work (macro_evals, noise draws, col
sharding), but they never interact numerically: the ADC floor, the ABN
gain and offset, the zero-point fold and the dequant are per output column
and g0 is per layer.  So the engine runs each row tile as one kernel call
over all of a device's col tiles, and the result is the per-col-tile
schedule's, bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core.mapping import LayerSpec
from repro.core.noise_model import NoiseConfig
from repro.runtime import engine as rt
from repro.runtime.program import program_for_plan

# 2 row tiles (1001 + 1000 rows: the last is smaller) x 5 col tiles of 60
SPEC = LayerSpec(m=8, k=2001, n=300, r_in=8, r_w=4, r_out=8)
M = 8


def _kernel_calls(jaxpr) -> int:
    """`cim_mbiw` pallas_calls in a jaxpr, counted once per dispatch (the
    branch compiled for a TPU; each dispatch also holds an interpreted
    branch for the CPU)."""
    n = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == "cim_mbiw"
                and not eqn.params["interpret"]):
            n += 1
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, jcore.ClosedJaxpr):
                    n += _kernel_calls(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    n += _kernel_calls(sub)
    return n


def _case(cfg, seed=0):
    plan = rt.plan_network([SPEC], cfg)
    params = rt.init_network_params(plan, jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (M, SPEC.k))
    return plan, params, x


@pytest.mark.parametrize("cfg", [
    rt.EngineConfig(),
    rt.EngineConfig(noise=NoiseConfig(enabled=True)),
    rt.EngineConfig(sharding=rt.ShardingConfig(devices=1)),
], ids=["clean", "noise", "col_mesh1"])
def test_one_kernel_call_per_row_tile(cfg):
    plan, params, x = _case(cfg)
    lp = plan.layers[0]
    assert len(lp.k_slices) == 2 and lp.k_slices[1][1] < lp.k_slices[0][1]
    assert len(lp.n_slices) == 5 and lp.macro_evals == 10
    if cfg.sharding is not None:
        assert lp.shard.kind == "col"
    binds = rt.bind_network(plan, params)
    key = jax.random.PRNGKey(2) if cfg.noise.enabled else None
    noise = cfg.noise if cfg.noise.enabled else None
    jaxpr = jax.make_jaxpr(lambda b, x, k: rt._exec_jit(
        plan, b, x, None, k, noise, None, None, bound=True,
        reference=False))(binds, x, key)
    assert _kernel_calls(jaxpr.jaxpr) == len(lp.k_slices)


NOISE = NoiseConfig(enabled=True)


@pytest.mark.parametrize("zp", ["scalar", "segments"])
@pytest.mark.parametrize("cfg", [
    rt.EngineConfig(),
    rt.EngineConfig(noise=NOISE),
    rt.EngineConfig(sharding=rt.ShardingConfig(devices=1)),
    rt.EngineConfig(noise=NOISE, sharding=rt.ShardingConfig(devices=1)),
], ids=["clean", "noise", "col_mesh1", "noise_col_mesh1"])
def test_merged_dispatch_bitexact_with_reference(cfg, zp):
    """The engine against the digital oracle on a multi-row x multi-col
    layer: scalar zero-point or per-row (segment) zero-points, clean or
    under one shared noise key, serial or on a 1-device col mesh."""
    plan, params, x = _case(cfg, seed=3)
    prog = program_for_plan(plan)
    segs = (jnp.arange(M, dtype=jnp.int32) // 3 if zp == "segments"
            else None)
    key = jax.random.PRNGKey(7) if cfg.noise.enabled else None
    y = prog.run(params, x, key, segments=segs)
    y_ref = prog.run(params, x, key, segments=segs, reference=True)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.parametrize("zp", ["scalar", "segments"])
def test_merged_dispatch_equals_per_col_tile_runs(zp):
    """An oracle that shares no dispatch with the merged call: each col
    tile run alone as a one-col-tile layer over its own columns (the same
    row tiles, g0 and activation codes) gives the merged layer's columns
    bit for bit."""
    plan, params, x = _case(rt.EngineConfig(), seed=5)
    lp = plan.layers[0]
    segs = (jnp.arange(M, dtype=jnp.int32) // 3 if zp == "segments"
            else None)
    y = np.asarray(program_for_plan(plan).run(params, x, segments=segs))
    for ns, nsz in lp.n_slices:
        ne = min(ns + nsz, SPEC.n)
        sub = rt.plan_network([LayerSpec(m=M, k=SPEC.k, n=ne - ns,
                                         r_in=8, r_w=4, r_out=8)])
        assert len(sub.layers[0].n_slices) == 1
        assert sub.layers[0].g0 == lp.g0
        p = {"w": params[0]["w"][:, ns:ne],
             "abn_log_gamma": params[0]["abn_log_gamma"][ns:ne],
             "abn_beta": params[0]["abn_beta"][ns:ne]}
        y_tile = program_for_plan(sub).run([p], x, segments=segs)
        np.testing.assert_array_equal(y[:, ns:ne], np.asarray(y_tile))
