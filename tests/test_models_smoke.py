"""Per-arch smoke tests (assignment requirement): reduced config, one
forward + one train step on CPU, output shapes + no NaNs; plus
train-vs-decode consistency for one arch per family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_archs, get_config, get_smoke_config
from repro.core.cim_layers import CIMConfig
from repro.launch.steps import init_train_state, make_train_step
from repro.models import transformer as tf
from repro.optim import AdamWConfig

B, S = 2, 24


def _batch(cfg, key):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "vlm":
        batch["prefix_embeds"] = jax.random.normal(
            key, (B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch["encoder_frames"] = jax.random.normal(
            key, (B, 32, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", all_archs())
def test_forward_shapes_finite(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = tf.init_params(cfg, key)
    batch = _batch(cfg, key)
    logits, _, aux = tf.forward(
        cfg, params, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"),
        encoder_frames=batch.get("encoder_frames"))
    s_out = S + (cfg.vision_tokens if cfg.family == "vlm" else 0)
    assert logits.shape == (B, s_out, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", all_archs())
def test_one_train_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(1)
    state = init_train_state(cfg, key)
    step = make_train_step(cfg, AdamWConfig(lr=1e-3))
    state, metrics = step(state, _batch(cfg, key))
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    for leaf in jax.tree.leaves(state["params"]):
        assert bool(jnp.all(jnp.isfinite(leaf))), "NaN in updated params"


@pytest.mark.parametrize("arch", all_archs())
def test_full_config_instantiable(arch):
    """FULL configs are exercised via eval_shape only (no allocation)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n_params > 1e8, f"{arch}: suspiciously small {n_params}"


@pytest.mark.parametrize("arch", ["granite_8b", "mixtral_8x22b",
                                  "mamba2_1_3b", "recurrentgemma_2b",
                                  "whisper_medium"])
def test_train_decode_consistency(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(2)
    params = tf.init_params(cfg, key)
    toks = jax.random.randint(key, (1, 8), 0, cfg.vocab_size)
    kwargs = {}
    cache_kwargs = {}
    if cfg.family == "audio":
        frames = jax.random.normal(key, (1, 16, cfg.d_model), jnp.bfloat16)
        kwargs["encoder_frames"] = frames
    full, _, _ = tf.forward(cfg, params, toks, **kwargs)
    cache = tf.init_cache(cfg, 1, max_len=16)
    outs = []
    for t in range(8):
        step_kwargs = dict(kwargs) if (cfg.family == "audio" and t == 0) else {}
        lg, cache, _ = tf.forward(cfg, params, toks[:, t:t + 1], cache=cache,
                                  **step_kwargs)
        outs.append(lg[:, 0])
    err = np.max(np.abs(np.asarray(full, np.float32)
                        - np.asarray(jnp.stack(outs, 1), np.float32)))
    assert err < 0.1, f"{arch}: train/decode divergence {err}"


def test_cim_fakequant_transformer():
    """The paper's technique on a transformer: forward+grad, finite."""
    cfg = get_smoke_config("granite_8b")
    cfg = cfg.replace(cim=CIMConfig(mode="fakequant", max_gamma=2.0**16))
    key = jax.random.PRNGKey(3)
    state = init_train_state(cfg, key)
    step = make_train_step(cfg, AdamWConfig(lr=1e-3))
    state, metrics = step(state, _batch(cfg, key))
    assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# the scanned stack's carried cache against a per-layer loop
# ---------------------------------------------------------------------------

def _layer_loop(cfg, params, tokens, caches, positions):
    """The decoder stack as a plain Python loop over its layers, each with
    its own one-layer cache, written by the same attention update: the
    reference for tf.forward's scan over the stacked cache."""
    x = tf.embed_tokens(cfg, params, tokens)
    stacks = [(params["layers"], False)]
    if cfg.dense_layers:
        stacks.insert(0, (params["dense_layers"], True))
    new = []
    for stack, dense in stacks:
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            p = jax.tree.map(lambda a: a[i], stack)
            y, c, _ = tf._decoder_layer(cfg, p, x, positions=positions,
                                        cache=caches[len(new)], layer=0,
                                        dense=dense)
            x = y.astype(x.dtype)
            new.append(c)
    return tf.lm_logits(cfg, params, x), new


def _one_layer(cfg, init, *args):
    return [init(cfg.replace(n_layers=1, dense_layers=0), *args)["layers"]
            for _ in range(cfg.n_layers)]


def _same_cache(cache, caches):
    """Every layer's slice of the stacked cache equals its own cache."""
    for i, c in enumerate(caches):
        for name, got in cache["layers"]["kv"].items():
            assert np.array_equal(np.asarray(got[i]),
                                  np.asarray(c["kv"][name][0])), (i, name)


CARRIED_CASES = {
    # a GQA stack whose sliding-window ring (4) wraps twice
    "ring": lambda: get_smoke_config("granite_8b").replace(sliding_window=4, dtype="float32"),
    # DeepSeek-V2-Lite: the dense prefix, then MoE layers, one latent stack
    "latent": lambda: get_smoke_config("deepseek-v2-lite").replace(
        dtype="float32"),
}


@pytest.mark.parametrize("case", sorted(CARRIED_CASES))
def test_carried_cache_matches_layer_loop(case):
    """A prompt, then one token a step, through tf.forward's scan over the
    stacked cache gives the per-layer loop's logits bit for bit, and each
    layer's slice of the stack holds what the loop wrote to that layer."""
    cfg = CARRIED_CASES[case]()
    b, p, t, length = 2, 3, 12, 12
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, t), 0,
                              cfg.vocab_size)
    cache = tf.init_cache(cfg, b, max_len=length)
    caches = _one_layer(cfg, tf.init_cache, b, length)
    fwd = jax.jit(lambda pr, x, c: tf.forward(cfg, pr, x, cache=c)[:2])
    ref = jax.jit(lambda pr, x, cs, pos: _layer_loop(
        cfg, pr, x, cs, pos + jnp.arange(x.shape[1])))
    pos = 0
    for chunk in [toks[:, :p]] + [toks[:, i:i + 1] for i in range(p, t)]:
        got, cache = fwd(params, chunk, cache)
        want, caches = ref(params, chunk, caches, pos)
        pos += chunk.shape[1]
        assert np.array_equal(np.asarray(got), np.asarray(want)), pos
    _same_cache(cache, caches)


def test_slot_cache_matches_layer_loop():
    """Two requests admitted into a slot-mapped cache at different
    offsets, decoded fused, one retired: tf.forward's scan over the
    stacked slot cache gives the per-layer loop's logits bit for bit."""
    from repro.models import common as cm

    cfg = get_smoke_config("granite_8b").replace(dtype="float32")
    slots, length = 2, 16
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    cache = tf.init_slot_cache(cfg, slots, length)
    caches = _one_layer(cfg, tf.init_slot_cache, slots, length)
    prefill = jax.jit(lambda pr, x: tf.forward(
        cfg, pr, x, cache=tf.init_cache(cfg, 1, max_len=length))[:2])
    step = jax.jit(lambda pr, x, c: tf.forward(
        cfg, pr, x[:, None], positions=c["pos"][:, None], cache=c)[:2])
    ref = jax.jit(lambda pr, x, cs, pos: _layer_loop(
        cfg, pr, x[:, None], cs, pos[:, None]))
    pos = jnp.zeros((slots,), jnp.int32)
    cur = jnp.zeros((slots,), jnp.int32)

    def decode(n):
        nonlocal cache, caches, cur
        for _ in range(n):
            got, cache = step(params, cur, cache)
            want, caches = ref(params, cur, caches, cache["pos"] - 1)
            assert np.array_equal(np.asarray(got), np.asarray(want))
            cur = jnp.argmax(got[:, -1], -1).astype(jnp.int32)

    for slot, n in ((0, 3), (1, 6)):
        prompt = jax.random.randint(jax.random.PRNGKey(10 + slot), (1, n),
                                    0, cfg.vocab_size)
        lg, c1 = prefill(params, prompt)
        cache = tf.write_slot_cache(cache, slot, c1)
        caches = [{"kv": cm.write_slot_kv(c["kv"], slot, {
            k: v[i:i + 1] for k, v in c1["layers"]["kv"].items()})}
            for i, c in enumerate(caches)]
        cur = cur.at[slot].set(jnp.argmax(lg[0, -1]).astype(jnp.int32))
        decode(2)
    cache = tf.free_slot_cache(cache, 0)
    caches = [{"kv": cm.free_slot_kv(c["kv"], 0)} for c in caches]
    decode(2)
    _same_cache(cache, caches)
