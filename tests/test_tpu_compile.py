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


# the decode step at the decode cells' cache shapes: the stacked cache
# rides the layer scan's carry, so its only cache-sized ops are the
# in-place writes of the step's token.  (d_model, n_heads, n_layers) cut
# to keep the compile short; batch, positions and the cache's per-token
# widths are the cells'.
OLMO_CUT = dict(n_layers=4, d_model=512, n_heads=4, n_kv_heads=4, d_ff=1024,
                vocab_size=1024)
SERVE_CASES = {
    # olmo-1b's decode cell: 64 x 384 positions, heads of 128
    "dense": ("olmo-1b", OLMO_CUT, 64, 384, False),
    # olmo-1b's prefill cell decoding: 8 x 1032, where XLA left free would
    # carry the stack in another layout than it is stored in
    "dense_b8": ("olmo-1b", OLMO_CUT, 8, 1032, False),
    # DeepSeek-V2-Lite: one dense layer, then three MoE; the latent cache
    # of kv_lora_rank 512 + qk_rope_head_dim 64 a token, 256 x 384
    "latent": ("deepseek-v2-lite", dict(n_layers=4, kv_lora_rank=512,
                                        qk_rope_head_dim=64),
               256, 384, False),
    # the slot-mapped cache of in-flight batching (per-slot cursors)
    "slot": ("olmo-1b", OLMO_CUT, 64, 384, True),
}


def _cache_sized_ops(text: str, cache) -> list:
    """Ops outside fusion bodies whose output (or any tuple part of it)
    holds as many cache-dtype elements as one layer's slice or the whole
    stack of some cache leaf — layout copies, restacked slices, copies of
    the whole cache — except in-place writes: a `dynamic-update-slice` or
    `scatter`, or a fusion whose root is one, of an update smaller than a
    layer."""
    import re

    import numpy as np

    dtype = {str(a.dtype) for a in jax.tree.leaves(cache) if a.ndim >= 3}
    hlo_dt = {"bfloat16": "bf16", "float32": "f32"}
    dts = {hlo_dt[d] for d in dtype}
    layer = {int(np.prod(a.shape[1:])) for a in jax.tree.leaves(cache)
             if a.ndim >= 3}
    sizes = layer | {int(np.prod(a.shape)) for a in jax.tree.leaves(cache)
                     if a.ndim >= 3}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    op_re = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)")

    def n_elems(dims: str) -> int:
        return int(np.prod([int(d) for d in dims.split(",") if d]))

    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.strip() not in ("", "}"):
            cur.append(line)
    bodies = set(re.findall(r"calls=%([\w.\-]+)", text))

    # operand that holds the written values, of each in-place write
    update_at = {"dynamic-update-slice": 1, "scatter": 2}

    def token_write(op: str, args: str, comp_lines) -> bool:
        lines, operands = comp_lines, args
        if op == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", args).group(1)
            lines = comps[called]
            root = next(op_re.match(ln) for ln in lines if "ROOT" in ln)
            op, operands = root.group(3), root.group(4)
        if op not in update_at:
            return False
        update = re.findall(r"%([\w.\-]+)", operands)[update_at[op]]
        shp = next(m.group(2) for m in map(op_re.match, lines)
                   if m and m.group(1) == update)
        return n_elems(shape_re.search(shp).group(2)) < min(layer)

    found = []
    for name, lines in comps.items():
        if name in bodies:
            continue
        for ln in lines:
            m = op_re.match(ln)
            if not m or m.group(3) in ("parameter", "bitcast", "tuple",
                                       "get-tuple-element", "while",
                                       "call", "conditional"):
                continue
            if any(dt in dts and n_elems(dims) in sizes
                   for dt, dims in shape_re.findall(m.group(2))):
                if not token_write(m.group(3), m.group(4), lines):
                    found.append(f"{m.group(3)} {m.group(1)} {m.group(2)}")
    return found


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_decode_step_writes_the_cache_in_place(one_chip, case):
    """The compiled, donated decode step copies no cache-sized buffer: no
    layout copy at its entry or exit, no per-layer slice restacked, no
    whole-cache copy — only the in-place writes of the step's token —
    and every cache leaf is aliased from input to output."""
    import re

    from repro.configs import get_config, get_smoke_config
    from repro.core.cim_layers import CIMConfig
    from repro.launch.steps import make_serve_step
    from repro.models import transformer as tf

    name, cut, batch, max_len, slot = SERVE_CASES[case]
    base = (get_smoke_config(name) if name == "deepseek-v2-lite"
            else get_config(name))
    cfg = base.replace(**cut, dtype="float32", cim=CIMConfig(
        mode="engine", max_gamma=2.0 ** 16))
    params = jax.eval_shape(lambda: tf.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    init = tf.init_slot_cache if slot else tf.init_cache
    cache = jax.eval_shape(lambda: init(cfg, batch, max_len))

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    step = jax.jit(make_serve_step(cfg), donate_argnums=(1,),
                   keep_unused=True)
    text = step.lower(jax.tree.map(shape, params), jax.tree.map(shape, cache),
                      shape(jax.ShapeDtypeStruct((batch, 1), jnp.int32))
                      ).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the TPU program"
    assert _cache_sized_ops(text, cache) == []
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    aliased = {int(p) for p in re.findall(r"\{[\d,]*\}: \((\d+),", alias)}
    first = len(jax.tree.leaves(params))
    n_cache = len(jax.tree.leaves(cache))
    assert set(range(first, first + n_cache)) <= aliased
