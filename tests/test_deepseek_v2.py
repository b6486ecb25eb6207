"""DeepSeek-V2-Lite on the normal path against the plain reference.

At `smoke_config()` sizes on seeded random weights: the program
(`tf.init_cache`, a prompt through `tf.forward`, then one token a step
through the latent cache) against `models/deepseek_ref.forward` in bypass
and in engine mode; the absorbed decode form against the expanded one;
the held-expert shares against the uncut layer; a skewed routing; and the
grouped `cim_mbiw` call against one `prog.serve` a group.

Tolerances.  `REL` = 1e-4 of the largest reference logit: program and
reference run the same float32 arithmetic in a different order (attention
sums, the absorbed against the expanded form, the experts' scatter-add),
which moves a logit by ~1e-6 of its scale here; one ADC code flipped
moves it by ~1e-2.  Each comparison also computes its bfloat16 control
(the latent cache in bfloat16, or the layer in bfloat16) and asserts that
it fails the same tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import mapping
from repro.core.cim_layers import (CIMConfig, _engine_config,
                                   cim_grouped_apply, group_block_rows,
                                   init_cim_grouped)
from repro.models import deepseek_ref as ref
from repro.models import mla, moe
from repro.models import transformer as tf
from repro.models.common import mlp_block
from repro.models.moe import held_layout, init_moe_held, moe_held_block
from repro.runtime.program import compile_program

REL = 1e-4
B, P, T = 2, 6, 10
ENGINE = CIMConfig(mode="engine", max_gamma=2.0 ** 16)


def _cfg(mode: str):
    cim = ENGINE if mode == "engine" else CIMConfig(mode="bypass")
    return get_smoke_config("deepseek-v2-lite").replace(dtype="float32",
                                                        cim=cim)


def _served(cfg, params, toks, cache_dtype):
    """Logits of a prompt of P ids prefilled into a fresh cache, then of
    each later token fed one a step."""
    fwd = jax.jit(lambda p, t, c: tf.forward(cfg, p, t, cache=c))
    cache = tf.init_cache(cfg, B, max_len=T, dtype=cache_dtype)
    lg, cache, _ = fwd(params, toks[:, :P], cache)
    outs = [lg]
    for t in range(P, T):
        lg, cache, _ = fwd(params, toks[:, t:t + 1], cache)
        outs.append(lg)
    return np.asarray(jnp.concatenate(outs, 1))


def _gap(got, want) -> float:
    """Largest logit difference over the largest reference logit."""
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.max(np.abs(want)))


@pytest.fixture(scope="module")
def toks():
    return jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              _cfg("bypass").vocab_size)


@pytest.mark.parametrize("mode", ["bypass", "engine"])
def test_prefill_then_decode_matches_reference(mode, toks):
    """The served logits against the reference's forward over the whole
    sequence (per-call ranges in engine mode), through a float32 cache;
    a bfloat16 cache is the control."""
    cfg = _cfg(mode)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    want = ref.forward(cfg, params, toks, cim=cfg.cim, prompt_len=P)
    assert _gap(_served(cfg, params, toks, jnp.float32), want) < REL
    assert _gap(_served(cfg, params, toks, jnp.bfloat16), want) > REL


def test_decode_matches_the_hf_full_forward(toks):
    """In float the latent cache and the absorbed form change nothing:
    prefill + decode equals HF's expanded full forward."""
    cfg = _cfg("bypass")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    want = ref.forward(cfg, params, toks)            # one call, expanded
    full = np.asarray(tf.forward(cfg, params, toks)[0])
    assert _gap(full, want) < REL
    assert _gap(_served(cfg, params, toks, jnp.float32), want) < REL
    assert _gap(_served(cfg, params, toks, jnp.bfloat16), want) > REL


def test_cache_holds_latents_only():
    """A token of a layer caches c_kv and k_pe: rank + rope values
    (c_kv (n_layers, B, L, rank), k_pe (n_layers, B, rope, L)), in one
    stack of every layer, the dense prefix's included."""
    cfg = get_smoke_config("deepseek-v2-lite")
    cache = tf.init_cache(cfg, 2, max_len=16)
    kv = cache["layers"]["kv"]
    assert set(kv) == {"c_kv", "k_pe", "idx"}
    assert kv["c_kv"].shape[:3] == (cfg.n_layers, 2, 16)
    assert kv["k_pe"].shape[:2] + kv["k_pe"].shape[3:] == (cfg.n_layers, 2,
                                                           16)
    per_token = kv["c_kv"].shape[-1] + kv["k_pe"].shape[-2]
    assert per_token == cfg.kv_lora_rank + cfg.qk_rope_head_dim
    full = jax.eval_shape(lambda: tf.init_cache(
        get_config("deepseek-v2-lite"), 1, 8))["layers"]["kv"]
    assert full["c_kv"].shape[0] == full["k_pe"].shape[0] == 27
    assert full["c_kv"].shape[-1] + full["k_pe"].shape[-2] == 576


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_absorbed_decode_equals_expanded(dtype):
    """One MLA block in float: a decode token through the absorbed form
    over the cache equals the expanded attention of the whole sequence at
    that position.  In bfloat16 it does not (the control)."""
    cfg = _cfg("bypass")
    p = mla.init_mla(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, cfg.d_model))
    pos = jnp.arange(T)
    want, _ = mla.mla_block(p, x, cfg, cfg.cim, positions=pos)
    dt = jnp.dtype(dtype)
    xd = x.astype(dt)
    pd = jax.tree.map(lambda a: a.astype(dt), p)
    cache = mla.init_latent_cache(1, B, T, cfg, dt)
    _, cache = mla.mla_block(pd, xd[:, :T - 1], cfg, cfg.cim,
                             positions=pos[:T - 1], cache=cache)
    got, _ = mla.mla_block(pd, xd[:, T - 1:], cfg, cfg.cim,
                           positions=pos[T - 1:], cache=cache)
    gap = _gap(got[:, 0].astype(jnp.float32), want[:, -1])
    assert (gap < REL) == (dtype == "float32"), gap


def _moe_case(held, share, seed=5, cim=None):
    cfg = _cfg("bypass")
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.moe_experts
    whole = init_moe_held(jax.random.PRNGKey(seed), d, f, e, e,
                          cfg.moe_shared_d_ff, cim)
    part = dict(whole, **{name: jax.tree.map(
        lambda a: a[share * held:(share + 1) * held], whole[name])
        for name in ("w_gate", "w_up", "w_down")})
    return cfg, whole, part


def _held(cfg, p, x, held, share, cim=None, dtype=jnp.float32):
    return moe_held_block(
        jax.tree.map(lambda a: a.astype(dtype), p), x.astype(dtype),
        n_experts=cfg.moe_experts, top_k=cfg.moe_top_k, held=held,
        share=share, norm_topk=cfg.moe_norm_topk,
        routed_scale=cfg.moe_routed_scale, cim=cim or cfg.cim)


@pytest.mark.parametrize("mode,held", [("bypass", 2), ("bypass", 4),
                                       ("engine", 4)])
def test_held_shares_add_up_to_the_uncut_layer(mode, held):
    """Every chip of an expert-parallel layer computes its held experts'
    part and the shared experts; the parts summed over the shares, the
    shared experts counted once, are the whole layer.  In engine mode too:
    an expert's range is over its own rows whichever experts a chip
    holds."""
    cim = ENGINE if mode == "engine" else None
    cfg, whole, _ = _moe_case(held, 0, cim=cim)
    e = cfg.moe_experts
    x = jax.random.normal(jax.random.PRNGKey(6), (B, T, cfg.d_model))
    want = _held(cfg, whole, x, e, 0, cim=cim)
    shared = mlp_block(whole["shared"], x, cim or cfg.cim)
    parts = [_held(cfg, _moe_case(held, s, cim=cim)[2], x, held, s,
                   cim=cim) for s in range(e // held)]
    got = sum(parts) - (e // held - 1) * shared
    assert _gap(got, want) < REL


def test_skewed_routing_drops_no_token():
    """A router that sends every token to expert 0 (and its next choices
    elsewhere): expert 0 gets all t routes, and every one is computed —
    the output equals each token's own routes summed in float."""
    cfg, whole, part = _moe_case(4, 0)
    d, e, k = cfg.d_model, cfg.moe_experts, cfg.moe_top_k
    router = whole["router"].at[:, 0].set(0.0)
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, d))
    x = x.at[..., 0].set(40.0)                       # a shared direction
    router = router.at[0, 0].set(1.0)
    part = dict(part, router=router)
    part.pop("shared")
    got = _held(cfg, part, x, 4, 0)
    xf = x.reshape(-1, d)
    probs = jax.nn.softmax(xf @ router, -1)
    top_p, top_idx = jax.lax.top_k(probs, k)
    assert bool(jnp.all(top_idx[:, 0] == 0))
    want = jnp.zeros_like(xf)
    for j in range(4):
        w = part["w_gate"]["w"][j], part["w_up"]["w"][j]
        y = (jax.nn.silu(xf @ w[0]) * (xf @ w[1])) @ part["w_down"]["w"][j]
        gate = jnp.sum(jnp.where(top_idx == j, top_p, 0.0), -1)
        want = want + gate[:, None] * y
    assert _gap(got.reshape(-1, d), want) < REL
    low = _held(cfg, part, x, 4, 0, dtype=jnp.bfloat16)
    assert _gap(low.reshape(-1, d).astype(jnp.float32), want) > REL


def test_held_layout_is_dropless_and_blocked():
    """Every route to a held expert gets its own row, grouped by expert,
    each group from a block boundary, in a buffer sized for all routes."""
    t, k, held, bm = 40, 3, 4, 8
    rng = np.random.default_rng(0)       # k distinct experts a token
    idx = jnp.asarray(np.stack([rng.choice(12, k, replace=False)
                                for _ in range(t)]))
    w = jnp.ones((t, k), jnp.float32)
    grp, tok, wt = held_layout(idx, w, held=held, share=1, bm=bm)
    grp, tok = np.asarray(grp), np.asarray(tok)
    local = np.asarray(idx) - held
    for g in range(held):
        rows = np.nonzero(grp == g)[0]
        want = np.nonzero(np.any(local == g, 1))[0]
        assert sorted(tok[rows]) == sorted(want)
        assert rows[0] % bm == 0 and np.all(np.diff(rows) == 1)
    assert grp.shape[0] % bm == 0
    assert float(np.asarray(wt).sum()) == float(np.sum((local >= 0)
                                                       & (local < held)))


@pytest.mark.parametrize("counts", [(3, 0, 130, 17), (1, 1, 1, 1)])
def test_grouped_call_bitexact_with_per_group_serve(counts):
    """One grouped `cim_mbiw` call over G weight sets equals G separate
    `prog.serve` calls of one CIM layer, bit for bit: each group's rows,
    range and weights are its own (an empty group included)."""
    g, k, n = len(counts), 80, 48
    bm = group_block_rows(ENGINE)
    params = init_cim_grouped(jax.random.PRNGKey(8), g, k, n, ENGINE)
    blocks = [-(-c // bm) for c in counts]
    rows = sum(blocks) * bm + bm                      # one spare block
    row_group = np.full(rows, g, np.int32)
    start = 0
    for i, (c, nb) in enumerate(zip(counts, blocks)):
        row_group[start:start + c] = i
        start += nb * bm
    x = jax.random.normal(jax.random.PRNGKey(9), (rows, k))
    got = np.asarray(jax.jit(lambda p, xx: cim_grouped_apply(
        p, xx, jnp.asarray(row_group), ENGINE))(params, x))
    for i in range(g):
        live = row_group == i
        if not live.any():
            continue
        spec = mapping.LayerSpec(m=int(live.sum()), k=k, n=n)
        prog = compile_program([spec], _engine_config(ENGINE))
        one = jax.tree.map(lambda a: a[i], params)
        want = prog.serve([one], x[live])
        np.testing.assert_array_equal(got[live], np.asarray(want))


def test_chunked_layout_matches_one_call(monkeypatch):
    """A layout served in chunks (as a prompt's is) gives the bits of one
    grouped call over all of it: the ranges are taken over the whole
    layout first."""
    cfg, whole, part = _moe_case(4, 1, cim=ENGINE)
    x = jax.random.normal(jax.random.PRNGKey(10), (B, T, cfg.d_model))

    def run():
        return np.asarray(_held(cfg, part, x, 4, 1, cim=ENGINE))

    one = run()
    monkeypatch.setattr(moe, "CHUNK_ROWS", group_block_rows(ENGINE))
    np.testing.assert_array_equal(run(), one)
