"""Engine-mode LLM projections: bit-exactness vs fakequant (ISSUE 7).

The acceptance bar: a transformer decoder stack and a `moe_block` with
`CIMConfig(mode="engine")` must be *bitwise* equal to the fakequant
training reference across the precision grid r_in {1,2,4,8} x r_w {1,2,4}
— jit against jit, including capacity-dropped tokens — and, under one
fixed noise key, the engine's Pallas kernel path must be bitwise equal to
its interpret-mode oracle and fully deterministic, unsharded and on a
4-device fake mesh.  Program-cache economics ride along: E experts share
ONE compiled program (>= E-fold serve reuse in `CIMProgram.stats()`).

Multi-device cases need fake CPU devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_llm_engine.py
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.cim_layers import CIMConfig
from repro.core.noise_model import NoiseConfig
from repro.models import transformer as tf
from repro.models.moe import init_moe, moe_block
from repro.runtime import engine as rt

N_DEV = len(jax.devices())
GRID = [(r_in, r_w) for r_in in (1, 2, 4, 8) for r_w in (1, 2, 4)]
NOISE_KEY = jax.random.PRNGKey(321)


def _need(devices: int) -> None:
    if N_DEV < devices:
        pytest.skip(f"needs {devices} devices, jax reports {N_DEV} (set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _moe_pair(r_in, r_w, *, cf=1.25, noise=None, sharding=None):
    """(params, x, fakequant cim, engine cim) for a small expert bank."""
    cim = CIMConfig(mode="fakequant", r_in=r_in, r_w=r_w)
    if noise is not None:
        cim = cim.replace(noise=noise)
    if sharding is not None:
        cim = cim.replace(sharding=sharding)
    params = init_moe(jax.random.PRNGKey(5), 16, 48, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 16), jnp.float32)
    return params, x, cim, cim.replace(mode="engine")


def _moe_out(params, x, cim, *, key=None, reference=False):
    fn = jax.jit(functools.partial(
        moe_block, n_experts=4, top_k=2, capacity_factor=1.25,
        cim=cim, reference=reference))
    out, _ = fn(params, x, key=key) if key is not None else fn(params, x)
    return np.asarray(out)


@pytest.mark.parametrize("r_in,r_w", GRID)
def test_moe_block_engine_bitexact_vs_fakequant(r_in, r_w):
    """The headline bugfix: engine mode runs the SAME quantized arithmetic
    as fakequant (no silent float fallback) — bitwise, jit vs jit."""
    params, x, cf_cim, en_cim = _moe_pair(r_in, r_w)
    a = _moe_out(params, x, cf_cim)
    b = _moe_out(params, x, en_cim)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("r_in,r_w", [(8, 4), (2, 1)])
def test_moe_block_engine_parity_with_capacity_drops(r_in, r_w):
    """Tokens dropped at the capacity limit drop identically in both
    modes (the capacity grid is digital glue shared by both paths)."""
    params, x, cf_cim, en_cim = _moe_pair(r_in, r_w)
    run = functools.partial(moe_block, n_experts=4, top_k=2, cim=cf_cim,
                            capacity_factor=0.4)   # forces drops
    a, _ = jax.jit(run)(params, x)
    b, _ = jax.jit(functools.partial(
        moe_block, n_experts=4, top_k=2, cim=en_cim,
        capacity_factor=0.4))(params, x)
    assert bool(jnp.all(jnp.isfinite(a)))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_unknown_cim_mode_raises():
    """The regression the issue names: an unsupported mode must raise,
    never silently serve unquantized float."""
    params, x, cim, _ = _moe_pair(4, 2)
    with pytest.raises(ValueError, match="does not support CIM mode"):
        moe_block(params, x, n_experts=4, top_k=2, capacity_factor=1.25,
                  cim=cim.replace(mode="sim"))


def _kernel_calls(jaxpr) -> int:
    """`cim_mbiw` dispatches in a jaxpr (the TPU branch of each)."""
    from jax.extend import core as jcore
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


@pytest.mark.parametrize("e", [4, 8])
def test_moe_engine_program_reuse_is_expertfold(e):
    """E experts share ONE grouped CIM call per bank: a moe_block traces
    one `cim_mbiw` dispatch per (bank, row tile) — gate, up and down are
    one row tile each at d=16, f=48 — however many experts it has, where
    a call per expert would trace 3E."""
    cim = CIMConfig(mode="engine", r_in=4, r_w=2)
    params = init_moe(jax.random.PRNGKey(5), 16, 48, e)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(functools.partial(
        moe_block, n_experts=e, top_k=2, capacity_factor=1.25,
        cim=cim))(params, x)
    assert _kernel_calls(jaxpr.jaxpr) == 3


@pytest.mark.parametrize("r_in,r_w", GRID)
def test_olmo_decoder_stack_engine_bitexact_vs_fakequant(r_in, r_w):
    """Full dense decoder stack (QKV/O + gated MLP through compiled
    programs, attention digital): engine == fakequant bitwise at every
    grid point, jit vs jit."""
    base = get_smoke_config("olmo-1b").replace(dtype="float32")
    cfq = base.replace(cim=base.cim.replace(
        mode="fakequant", r_in=r_in, r_w=r_w))
    cen = base.replace(cim=base.cim.replace(
        mode="engine", r_in=r_in, r_w=r_w))
    params = tf.init_params(cfq, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                base.vocab_size)
    a = jax.jit(lambda p, t: tf.forward(cfq, p, t)[0])(params, tokens)
    b = jax.jit(lambda p, t: tf.forward(cen, p, t)[0])(params, tokens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("r_in,r_w", [(8, 4), (1, 2)])
def test_phi35_moe_stack_engine_bitexact_vs_fakequant(r_in, r_w):
    """The MoE decoder stack end to end: router + capacity grouping +
    per-expert programs match fakequant bitwise."""
    base = get_smoke_config("phi3.5-moe-42b-a6.6b").replace(dtype="float32")
    cfq = base.replace(cim=base.cim.replace(
        mode="fakequant", r_in=r_in, r_w=r_w))
    cen = base.replace(cim=base.cim.replace(
        mode="engine", r_in=r_in, r_w=r_w))
    params = tf.init_params(cfq, jax.random.PRNGKey(2))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 0,
                                base.vocab_size)
    a = jax.jit(lambda p, t: tf.forward(cfq, p, t)[0])(params, tokens)
    b = jax.jit(lambda p, t: tf.forward(cen, p, t)[0])(params, tokens)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- fixed noise key -------------------------------------------------------

def _noise_case(devices: int):
    sh = rt.ShardingConfig(devices=devices) if devices else None
    return _moe_pair(4, 2, noise=NoiseConfig(), sharding=sh)


@pytest.mark.parametrize("devices", [0, 4])
def test_moe_engine_noise_kernel_matches_reference(devices):
    """Under one fixed noise key the engine's Pallas kernel path equals
    its interpret-mode oracle bitwise, and the draws are deterministic —
    unsharded and across the 4-macro fake mesh."""
    if devices:
        _need(devices)
    params, x, _, en_cim = _noise_case(devices)
    a = _moe_out(params, x, en_cim, key=NOISE_KEY)
    b = _moe_out(params, x, en_cim, key=NOISE_KEY, reference=True)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, _moe_out(params, x, en_cim,
                                              key=NOISE_KEY))
    other = _moe_out(params, x, en_cim, key=jax.random.PRNGKey(77))
    assert np.any(a != other), "noise key had no effect"


def test_olmo_engine_noise_deterministic():
    """Noise-keyed engine decode on the dense stack: same key -> bitwise
    identical logits; different key -> different logits."""
    base = get_smoke_config("olmo-1b").replace(dtype="float32")
    cen = base.replace(cim=base.cim.replace(
        mode="engine", r_in=4, r_w=2, noise=NoiseConfig()))
    params = tf.init_params(cen, jax.random.PRNGKey(4))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 8), 0,
                                base.vocab_size)
    f = jax.jit(lambda p, t, k: tf.forward(cen, p, t, key=k)[0])
    a = f(params, tokens, NOISE_KEY)
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(f(params, tokens, NOISE_KEY)))
    assert np.any(np.asarray(a)
                  != np.asarray(f(params, tokens, jax.random.PRNGKey(9))))
