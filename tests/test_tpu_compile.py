"""Main-path kernels compiled for a TPU v5e that the compiler describes.

No chip is needed: `topologies.get_topology_desc` describes a v5e and the
installed TPU compiler compiles for it, so these tests catch what the
Pallas interpreter cannot — block shapes the chip refuses, primitives
Mosaic has no lowering for — and check that a program lowered for the
TPU carries the Mosaic kernel (`tpu_custom_call`), never the interpreter.
This is the only test file that loads the TPU compiler; the topology is
described inside a fixture, never at import.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cim_mbiw import ops
from repro.kernels.flash_attn.ops import ring_decode_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler here: nothing to check
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the TPU program"
    return text


# (r_in, rows, k, n): a full 1152x256 macro tile at two planes (r_in=8)
# and one plane (r_in=4); LeNet's conv tiles at r_in=8, whose K (9, 144)
# is no multiple of 128
CIM_TILES = [(8, 256, 1152, 256), (4, 256, 1152, 256),
             (8, 1024 * 14 * 14, 144, 32), (8, 1024 * 28 * 28, 9, 16)]


@pytest.mark.parametrize("fuse_adc", [True, False], ids=["fused", "raw_dp"])
@pytest.mark.parametrize("r_in,rows,k,n", CIM_TILES[:2])
def test_cim_mbiw_macro_tile_compiles(one_chip, r_in, rows, k, n,
                                      fuse_adc):
    _compile_cim(one_chip, r_in, rows, k, n, fuse_adc)


@pytest.mark.parametrize("r_in,rows,k,n", CIM_TILES[2:])
def test_cim_mbiw_lenet_conv_tile_compiles(one_chip, r_in, rows, k, n):
    prec = ops.KernelPrecision(r_in, 4, 8)
    assert prec.n_planes == 2
    _compile_cim(one_chip, r_in, rows, k, n, True)


def _compile_cim(dev, r_in, rows, k, n, fuse_adc):
    fn = ops.kernel_variant_for_tile(ops.KernelPrecision(r_in, 4, 8), rows,
                                     k, n, bm=128, bn=128, bk=256,
                                     fuse_adc=fuse_adc)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=dev)

    _compiled_text(lambda x, w, g, b: fn(x, w, g, b, 0.01),
                   shape((rows, k), jnp.int32), shape((k, n), jnp.int32),
                   shape((n,), jnp.float32), shape((n,), jnp.float32))


# one row tile dispatched over a whole projection's columns at olmo-1b's
# widths, with the blocks the engine's defaults fit to it: a decode step's
# widest (64 rows, per-row beta) and a prefill of 64 x 128 prompt tokens
ROW_TILE_CALLS = [(64, 1024, 8192, True, (64, 512, 1024)),
                  (8192, 1024, 2048, False, (128, 512, 1024))]


@pytest.mark.parametrize("rows,k,n,per_row_beta,blocks", ROW_TILE_CALLS,
                         ids=["decode", "prefill"])
def test_cim_mbiw_row_tile_dispatch_compiles(one_chip, rows, k, n,
                                             per_row_beta, blocks):
    from repro.runtime.engine import EngineConfig

    cfg, prec = EngineConfig(), ops.KernelPrecision(8, 4, 8)
    assert ops.fit_blocks(prec.n_planes, rows, k, n, cfg.bm, cfg.bn,
                          cfg.bk) == blocks
    fn = ops.kernel_variant_for_tile(prec, rows, k, n, bm=cfg.bm, bn=cfg.bn,
                                     bk=cfg.bk)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    beta = (rows, n) if per_row_beta else (n,)
    _compiled_text(lambda x, w, g, b: fn(x, w, g, b, 0.01),
                   shape((rows, k), jnp.int32), shape((k, n), jnp.float32),
                   shape((n,), jnp.float32), shape(beta, jnp.float32))


def test_ring_decode_attention_compiles_at_olmo_heads(one_chip):
    r, n_l, h, hd = 4, 16, 16, 128

    def shape(s):
        return jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)

    _compiled_text(ring_decode_attention, shape((r, h, hd)),
                   shape((r, n_l, h, hd)), shape((r, n_l, h, hd)),
                   shape((r, n_l)))


def test_cim_kernel_call_carries_its_scope(one_chip):
    """The Mosaic call of a bound engine executable is charged to the
    `cim.kernel` scope in a TPU trace: its op_name holds the scope."""
    import re

    from repro.core import mapping
    from repro.runtime import engine as rt

    plan = rt.plan_network([mapping.LayerSpec(m=256, k=1152, n=256)])
    binds = jax.eval_shape(lambda: rt.bind_network(
        plan, rt.init_network_params(plan, jax.random.PRNGKey(0))))

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    text = _compiled_text(
        lambda b, x, m: rt._exec_jit(plan, b, x, m, None, None, None, None,
                                     bound=True, reference=False),
        jax.tree.map(shape, binds),
        shape(jax.ShapeDtypeStruct((256, 1152), jnp.float32)),
        shape(jax.ShapeDtypeStruct((), jnp.int32)))
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "/cim.kernel/" in op_name, op_name


# grouped calls at dsv2lite.decode.b256's shapes, one row tile each: the 8
# held experts' gate/up (2048 -> 1408, row tile K 1024) over the dropless
# layout of 256 x 6 routes in 20 blocks of 128 rows, and the 16 heads'
# absorbed q_lat product (128 -> 512) over 256 rows a head
GROUPED_CALLS = [(8, 20, 1024, 1408), (16, 32, 128, 512)]


@pytest.mark.parametrize("groups,blocks,k,n", GROUPED_CALLS,
                         ids=["held_experts", "absorbed_heads"])
def test_cim_mbiw_grouped_call_compiles(one_chip, groups, blocks, k, n):
    from repro.runtime.engine import EngineConfig

    cfg = EngineConfig()
    rows = blocks * cfg.bm

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    def call(x, w, g, b, grp, src, live):
        return ops.cim_matmul_grouped(x, w, g, b, grp, src, live, r_in=8,
                                      r_out=8, g0=0.01, bm=cfg.bm,
                                      bn=cfg.bn, bk=cfg.bk)

    idx = shape((blocks,), jnp.int32)
    _compiled_text(call, shape((rows, k), jnp.float32),
                   shape((groups, k, n), jnp.float32),
                   shape((blocks, n), jnp.float32),
                   shape((blocks, n), jnp.float32), idx, idx, idx)
