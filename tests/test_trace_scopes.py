"""Device scopes of the CIM program: every op of the hot path carries its
program layer in the compiled HLO's `op_name` metadata, where a profiler
trace finds it, and the scopes change nothing that runs.

The taxonomy is listed in `runtime/engine.py`'s module docstring.  An op
belongs to the innermost taxonomy scope of its `op_name`.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import mapping
from repro.core.noise_model import NoiseConfig
from repro.launch.steps import make_serve_step
from repro.models import transformer as tf
from repro.runtime import engine as rt
from repro.runtime.program import BatchBuckets, compile_program

B = 2
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost `cim.*`/`lm.*` component of an op_name path."""
    inner = [p for p in op_name.split("/") if p.startswith(("cim.", "lm."))]
    return inner[-1] if inner else "(unscoped)"


def scoped_prims(hlo_text: str) -> dict:
    """{scope: set of the primitives (last op_name component) under it}."""
    out = {}
    for name in _OP_NAME.findall(hlo_text):
        out.setdefault(scope_of(name), set()).add(name.rsplit("/", 1)[-1])
    return out


def _cnn_program(noise=None):
    specs = [mapping.conv_layer_spec(batch=B, h=8, w=8, c_in=1, c_out=4,
                                     kh=3, kw=3, stride=1,
                                     padding=((1, 1), (1, 1)),
                                     r_in=8, r_w=4, r_out=8),
             mapping.LayerSpec(m=B, k=4 * 4 * 4, n=10, r_in=8, r_w=4,
                               r_out=8)]
    cfg = rt.EngineConfig() if noise is None else rt.EngineConfig(
        noise=noise)
    prog = compile_program(specs, cfg, activations=["relu", "none"],
                           pools=[2, 1], buckets=BatchBuckets(min_bucket=B))
    params = prog.init_params(jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (B, 8, 8, 1))
    return prog, params, x


def _exec_text(prog, params, x, bound: bool, key=None, noise=None) -> str:
    payload = (list(rt.bind_network(prog.plan, params)) if bound
               else params)
    return rt._exec_jit.lower(
        prog.plan, payload, x, jnp.asarray(B, jnp.int32), key, noise,
        None, None, bound=bound, reference=False).compile().as_text()


@pytest.fixture(scope="module")
def cnn():
    return _cnn_program()


@pytest.mark.parametrize("bound", [False, True], ids=["unbound", "bound"])
def test_engine_ops_carry_their_scope(cnn, bound):
    prog, params, x = cnn
    got = scoped_prims(_exec_text(prog, params, x, bound))
    assert "conv_general_dilated" in got["cim.im2col"]       # im2col
    assert {"reduce_min", "reduce_max"} <= got["cim.act_quant"]
    assert "reduce_sum" in got["cim.zp_fold"]                # column sums
    assert "concatenate" in got["cim.planes"]                # plane split
    assert {"div", "slice"} <= got["cim.recombine"]          # dequant, unpad
    assert "reduce_window_max" in got["cim.epilogue"]        # max-pool
    assert got["cim.kernel"]        # the interpreted kernel's ops
    # weight quantization runs in the executable only when unbound
    assert ("cim.bind" in got) is not bound
    assert "cim.noise" not in got


def test_bound_program_bind_is_scoped(cnn):
    prog, params, _ = cnn
    text = jax.jit(lambda p: rt.bind_network(prog.plan, p)).lower(
        params).compile().as_text()
    assert set(scoped_prims(text)) - {"(unscoped)"} == {"cim.bind"}


def test_noise_epilogue_is_scoped():
    prog, params, x = _cnn_program(noise=NoiseConfig())
    text = _exec_text(prog, params, x, True, key=jax.random.PRNGKey(2),
                      noise=prog.plan.cfg.noise)
    assert "floor" in scoped_prims(text)["cim.noise"]        # noisy ADC
    # the thermal draws
    assert any(scope_of(n) == "cim.noise" and "jit(_normal)" in n
               for n in _OP_NAME.findall(text))


def test_decode_step_ops_carry_their_scope():
    cfg = get_smoke_config("olmo-1b").replace(dtype="float32")
    cfg = cfg.replace(cim=cfg.cim.replace(mode="engine", r_in=8, r_w=4))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    cache = tf.init_cache(cfg, B, max_len=8)
    tokens = jnp.zeros((B, 1), jnp.int32)
    text = jax.jit(make_serve_step(cfg)).lower(
        params, cache, tokens).compile().as_text()
    got = scoped_prims(text)
    assert got["lm.embed"]
    assert {"reduce_sum", "rsqrt"} & got["lm.norm"]
    assert "exp" in got["lm.attention"]                      # softmax
    assert "dynamic_update_slice" in got["lm.kv_write"]      # KV cache
    # logits, and the argmax as XLA's iota + variadic reduce
    assert {"dot_general", "iota", "reduce"} <= got["lm.head"]
    names = _OP_NAME.findall(text)
    assert any(scope_of(n) == "lm.layer" and "silu" in n
               for n in names)                               # SwiGLU
    # the projections inside a decoder layer keep their engine scopes:
    # the innermost taxonomy scope wins over lm.layer
    assert any("lm.layer" in n and scope_of(n) == "cim.kernel"
               for n in names)
    assert any("lm.layer" in n and scope_of(n) == "cim.zp_fold"
               for n in names)
    assert "cim.bind" in got        # the LM serves unbound params


def test_scopes_add_no_trace_or_executable(cnn, tmp_path):
    prog, params, x = cnn
    bound = prog.bind(params)
    y0 = np.asarray(bound.serve(x))                   # warm
    traces = rt.TRACE_COUNT["n"]
    stats = prog.stats()
    jax.profiler.start_trace(str(tmp_path))           # repro.serve is live
    try:
        y1 = np.asarray(bound.serve(x))
    finally:
        jax.profiler.stop_trace()
    y2 = np.asarray(bound.serve(x))
    assert rt.TRACE_COUNT["n"] == traces
    after = prog.stats()
    assert after["executables_compiled"] == stats["executables_compiled"]
    assert after["bucket_hits"] == stats["bucket_hits"] + 2
    np.testing.assert_array_equal(y0, y1)
    np.testing.assert_array_equal(y0, y2)
    np.testing.assert_array_equal(
        y0, np.asarray(prog.run(params, x, reference=True)))
