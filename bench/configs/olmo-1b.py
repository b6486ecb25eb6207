"""olmo-1b: weights from the seed, least work from shapes, and the plain
reference of the served model.

The reference is OLMo-1B's decoder (arXiv:2402.00838: non-parametric
layer norm, rotary attention, SwiGLU MLP, tied head) with every projection
through the CIM layer of bench/cim.py, in float32, its digital dots
(attention and the tied head) at the configuration's `dot_precision`.  Like the served program it takes activation ranges per call:
over all prompt rows of the batch for the prefill, and over the batch's
rows of one position for each decode step.  Keys and values are rounded
to the configuration's cache dtype before attention reads them."""
from __future__ import annotations

import json
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import cim
from traffic import key_of

PROJECTIONS = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
               ("attn", "wo"), ("mlp", "w_up"), ("mlp", "w_gate"),
               ("mlp", "w_down"))


def point(cfg: dict) -> cim.Point:
    p = cfg["point"]
    return (p["r_in"], p["r_w"], p["r_out"])


def _shape(cfg: dict, name: str):
    m = cfg["model"]
    d, f = m["d_model"], m["d_ff"]
    hd = d // m["n_heads"]
    kv = m["n_kv_heads"] * hd
    return {"wq": (d, m["n_heads"] * hd), "wk": (d, kv), "wv": (d, kv),
            "wo": (m["n_heads"] * hd, d), "w_up": (d, f), "w_gate": (d, f),
            "w_down": (f, d)}[name]


def gemms(cfg: dict, rows: int) -> List[tuple]:
    """(m, k, n) of every CIM GEMM of one forward over `rows` token rows."""
    one = [(rows,) + _shape(cfg, name) for _, name in PROJECTIONS]
    return one * cfg["model"]["n_layers"]


def model_ops(cfg: dict, tokens: int, context: int, head_rows: int) -> int:
    """Operations a forward needs: the projections of `tokens` tokens,
    attention over `context` (the sum over those tokens of the positions
    each attends to), and the tied head over `head_rows` rows."""
    m = cfg["model"]
    per_token = sum(k * n for _, k, n in gemms(cfg, 1))
    attn = 2 * m["n_heads"] * (m["d_model"] // m["n_heads"]) \
        * m["n_layers"]
    return 2 * (per_token * tokens + attn * context
                + m["d_model"] * m["vocab_size"] * head_rows)


def make_params(cfg: dict, seed: int):
    """The served model's parameters in the program's layout (layers
    stacked on a leading axis), made on the device in one call."""
    m, pt = cfg["model"], point(cfg)
    n_layers, d = m["n_layers"], m["d_model"]

    def layer(key):
        keys = jax.random.split(key, len(PROJECTIONS))
        p = {"ln1": {}, "ln2": {}, "attn": {}, "mlp": {}}
        for kk, (group, name) in zip(keys, PROJECTIONS):
            k, n = _shape(cfg, name)
            p[group][name] = cim.init_linear(kk, k, n, pt, cfg["max_gamma"])
        return p

    @jax.jit
    def make(key):
        ke, kl = jax.random.split(key)
        return {"embed": d ** -0.5 * jax.random.normal(
                    ke, (m["vocab_size"], d), jnp.float32),
                "final_norm": {},
                "layers": jax.vmap(layer)(jax.random.split(kl, n_layers))}

    return make(key_of(seed, 3))


def _norm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv           # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _layer(cfg: dict, p, x, seg, n_seg, dtype):
    """One decoder layer over (B, T, d), T positions from 0."""
    m, pt, g = cfg["model"], point(cfg), cfg["max_gamma"]
    b, t, d = x.shape
    h = m["n_heads"]
    hd = d // h

    def proj(v, w):
        y = cim.linear(v.reshape(b * t, -1), w, pt, g, seg, n_seg, dtype)
        return y.reshape(b, t, -1)

    eps = cfg["norm_eps"]
    xn = _norm(x, eps)
    pos = jnp.arange(t)
    q = _rope(proj(xn, p["attn"]["wq"]).reshape(b, t, h, hd), pos,
              m["rope_theta"])
    kv_dtype = jnp.dtype(cfg["kv_dtype"])
    k = _rope(proj(xn, p["attn"]["wk"]).reshape(b, t, h, hd), pos,
              m["rope_theta"]).astype(kv_dtype).astype(dtype)
    v = proj(xn, p["attn"]["wv"]).reshape(b, t, h, hd).astype(
        kv_dtype).astype(dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q / jnp.sqrt(dtype(hd)), k)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    x = x + proj(a.reshape(b, t, d), p["attn"]["wo"])
    xn = _norm(x, eps)
    hid = jax.nn.silu(proj(xn, p["mlp"]["w_gate"])) * proj(xn, p["mlp"]["w_up"])
    return x + proj(hid, p["mlp"]["w_down"])


# one compiled reference layer per (configuration, decode length, dtype)
_LAYER_JITS: dict = {}


def hidden(cfg: dict, params, tokens: jnp.ndarray, prompt_len: int,
           dtype=jnp.float32) -> jnp.ndarray:
    """Normalized final hidden states (B, T - prompt_len + 1, d) at the
    positions whose logits choose a served token: the prompt's last, and
    each fed-back token's.  tokens = prompt ++ served tokens but the last.

    Rows quantize together as the program's calls do: all prompt rows of
    the batch in one segment, then one segment per decode position."""
    b, t = tokens.shape
    seg = np.concatenate([np.zeros(prompt_len, np.int32),
                          np.arange(1, t - prompt_len + 1, dtype=np.int32)])
    seg = jnp.asarray(np.tile(seg, b))
    n_seg = t - prompt_len + 1
    key = (json.dumps(cfg, sort_keys=True), n_seg, jnp.dtype(dtype).name)
    if key not in _LAYER_JITS:
        _LAYER_JITS[key] = jax.jit(lambda layers, i, x, s: _layer(
            cfg, jax.tree.map(lambda a: a[i], layers), x, s, n_seg, dtype))
    layer = _LAYER_JITS[key]
    with jax.default_matmul_precision(cfg["dot_precision"]):
        x = params["embed"][tokens].astype(dtype)
        for i in range(cfg["model"]["n_layers"]):
            x = layer(params["layers"], jnp.int32(i), x, seg)
        return _norm(x[:, prompt_len - 1:], cfg["norm_eps"])


def logits(cfg: dict, params, h: jnp.ndarray) -> jnp.ndarray:
    """Tied head over a block of final hidden states (..., d)."""
    with jax.default_matmul_precision(cfg["dot_precision"]):
        return h @ params["embed"].T.astype(h.dtype)
