"""deepseek-v2-lite: weights from the seed, least work from shapes, and the
plain reference of the served model.

The reference is DeepSeek-V2-Lite's decoder as HF `modeling_deepseek`
writes it (arXiv:2405.04434 §2): MLA with YaRN rope in HF's pair layout,
a leading dense SwiGLU layer, then MoE layers of greedy softmax top-6
routed experts (no renormalisation, routed scaling 1) plus two shared
experts, RMSNorm, an untied head.  Every projection goes through the CIM
layer of bench/cim.py in float32; the digital dots (attention, head) run
at the configuration's `dot_precision`, the router at HIGHEST.  Like the
served program it takes activation ranges per call: over all prompt rows
of the batch, then over the batch's rows of one position a decode step;
a head's absorbed product and an expert take theirs over their own rows.
Latents are rounded to the cache dtype before attention reads them.

Departures from HF, as the served program makes them: only the held
experts [share*held, (share+1)*held) are computed (routes to the others
add nothing); the prompt's keys and values are expanded through kv_b,
while each later position attends in the absorbed form
q_lat = q_nope . W_UK^T, o = (softmax . c_kv) . W_UV, both CIM products
with weights sliced from kv_b and their own ABN (`uk`, `uv`); the router
is digital."""
from __future__ import annotations

import json
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import cim
from traffic import key_of

# decode positions the reference computes at once (attention and FFN)
POSITION_BLOCK = 32


def point(cfg: dict) -> cim.Point:
    p = cfg["point"]
    return (p["r_in"], p["r_w"], p["r_out"])


def _sizes(cfg: dict) -> dict:
    m = cfg["model"]
    return dict(d=m["d_model"], h=m["n_heads"], r=m["kv_lora_rank"],
                nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
                v=m["v_head_dim"], f=m["d_ff"], fe=m["moe_d_ff"],
                fs=m["moe_shared_d_ff"], e=m["moe_experts"],
                held=m["moe_held"], k=m["moe_top_k"],
                n_dense=m["dense_layers"],
                n_moe=m["n_layers"] - m["dense_layers"])


def gemms(cfg: dict, rows: int) -> List[tuple]:
    """(m, k, n) of every CIM GEMM of one forward over `rows` token rows.

    Attention is counted in decode's absorbed form (16 heads of q_lat,
    128 -> 512, and of the output, 512 -> 128); a prompt call expands
    through kv_b (512 -> 16 x 256) instead, with the same operations and
    weight bytes.  A held expert's GEMMs are counted at its expected load,
    rows x top_k / n_experts rows (so the held experts' ops are
    rows x 6 x 8 / 64), and its weight bytes once a call."""
    s = _sizes(cfg)
    d, h = s["d"], s["h"]
    attn = ([(rows, d, h * (s["nope"] + s["rope"])),
             (rows, d, s["r"] + s["rope"]), (rows, h * s["v"], d)]
            + [(rows, s["nope"], s["r"]), (rows, s["r"], s["v"])] * h)
    dense = [(rows, d, s["f"]), (rows, d, s["f"]), (rows, s["f"], d)]
    m = rows * s["k"] / s["e"]
    experts = [(m, d, s["fe"]), (m, d, s["fe"]), (m, s["fe"], d)] \
        * s["held"]
    shared = [(rows, d, s["fs"]), (rows, d, s["fs"]), (rows, s["fs"], d)]
    return ((attn + dense) * s["n_dense"]
            + (attn + experts + shared) * s["n_moe"])


def model_ops(cfg: dict, tokens: int, context: int, head_rows: int) -> int:
    """Operations a forward needs: the projections of `tokens` tokens (the
    held experts at their expected load), attention over `context` (the
    sum over those tokens of the positions each attends to) in the
    absorbed form, 16 heads x (512 + 64 + 512) multiply-adds a position,
    and the head over `head_rows` rows."""
    s = _sizes(cfg)
    per_token = sum(k * n * mm for mm, k, n in gemms(cfg, 1))
    attn = s["h"] * (2 * s["r"] + s["rope"]) * (s["n_dense"] + s["n_moe"])
    return int(2 * (per_token * tokens + attn * context
                    + s["d"] * cfg["model"]["vocab_size"] * head_rows))


def make_params(cfg: dict, seed: int):
    """The served model's parameters in the program's layout (the dense
    layer and the MoE layers each stacked on a leading axis), made on the
    device in one call."""
    s, pt, g = _sizes(cfg), point(cfg), cfg["max_gamma"]
    d, h, r = s["d"], s["h"], s["r"]

    def lin(key, k, n):
        return cim.init_linear(key, k, n, pt, g)

    def grouped(key, n_groups, k, n):
        return jax.vmap(lambda kk: lin(kk, k, n))(
            jax.random.split(key, n_groups))

    def abn(k, n):
        return {"abn_log_gamma": jnp.full(
                    (h, n), cim.log_gamma_init(k, pt, g), jnp.float32),
                "abn_beta": jnp.zeros((h, n), jnp.float32)}

    def mlp(key, f):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"w_up": lin(k1, d, f), "w_down": lin(k2, f, d),
                "w_gate": lin(k3, d, f)}

    def attn(key):
        ks = jax.random.split(key, 4)
        return {"wq": lin(ks[0], d, h * (s["nope"] + s["rope"])),
                "wkv_a": lin(ks[1], d, r + s["rope"]),
                "kv_norm": {"scale": jnp.ones((r,), jnp.float32)},
                "wkv_b": lin(ks[2], r, h * (s["nope"] + s["v"])),
                "wo": lin(ks[3], h * s["v"], d),
                "uk": abn(s["nope"], r), "uv": abn(r, s["v"])}

    def norms():
        return {"scale": jnp.ones((d,), jnp.float32)}

    def dense_layer(key):
        ka, km = jax.random.split(key)
        return {"ln1": norms(), "ln2": norms(), "attn": attn(ka),
                "mlp": mlp(km, s["f"])}

    def moe_layer(key):
        ka, kr, kg, ku, kd, ks = jax.random.split(key, 6)
        return {"ln1": norms(), "ln2": norms(), "attn": attn(ka),
                "moe": {"router": d ** -0.5 * jax.random.normal(
                            kr, (d, s["e"]), jnp.float32),
                        "w_gate": grouped(kg, s["held"], d, s["fe"]),
                        "w_up": grouped(ku, s["held"], d, s["fe"]),
                        "w_down": grouped(kd, s["held"], s["fe"], d),
                        "shared": mlp(ks, s["fs"])}}

    @jax.jit
    def make(key):
        ke, kh, kd, km = jax.random.split(key, 4)
        vocab = cfg["model"]["vocab_size"]
        return {"embed": d ** -0.5 * jax.random.normal(
                    ke, (vocab, d), jnp.float32),
                "final_norm": norms(),
                "lm_head": lin(kh, d, vocab),
                "dense_layers": jax.vmap(dense_layer)(
                    jax.random.split(kd, s["n_dense"])),
                "layers": jax.vmap(moe_layer)(
                    jax.random.split(km, s["n_moe"]))}

    return make(key_of(seed, 3))


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(x.dtype)


def _yarn(cfg: dict):
    """(inverse frequencies, cos/sin scale, softmax scale) of HF's
    DeepseekV2YarnRotaryEmbedding and attention."""
    m, sc = cfg["model"], cfg["rope_scaling"]
    dim, base = m["qk_rope_head_dim"], m["rope_theta"]
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]

    def mscale(scale, ms):
        return 1.0 if scale <= 1 else 0.1 * ms * math.log(scale) + 1.0

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(
            base))

    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra, inter = 1.0 / base ** exps, 1.0 / (factor * base ** exps)
    low = max(math.floor(corr(sc["beta_fast"])), 0)
    high = min(math.ceil(corr(sc["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    keep = 1.0 - np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                         / (high - low), 0, 1)
    inv = (inter * (1 - keep) + extra * keep).astype(np.float32)
    cs = mscale(factor, sc["mscale"]) / mscale(factor, sc["mscale_all_dim"])
    soft = (m["qk_nope_head_dim"] + dim) ** -0.5 \
        * mscale(factor, sc["mscale_all_dim"]) ** 2
    return inv, cs, soft


def _rope(x, pos, inv, cs):
    """x (B, T, H, R): pair i is (x[2i], x[2i+1]); out = rotated evens,
    then rotated odds (HF's de-interleaved layout)."""
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)
    cos = (jnp.cos(ang) * cs)[None, :, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * cs)[None, :, None, :].astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(cfg: dict, p, x, seg, n_seg, prompt_len: int, dtype):
    """x + MLA(x) over (B, T, d), positions 0..T-1: the prompt attends in
    HF's expanded form, each later position in the absorbed form (module
    docstring)."""
    s, pt, g = _sizes(cfg), point(cfg), cfg["max_gamma"]
    b, t, d = x.shape
    h, r, nope, rope, vd = s["h"], s["r"], s["nope"], s["rope"], s["v"]
    eps, kv_dtype = cfg["norm_eps"], jnp.dtype(cfg["kv_dtype"])

    def proj(v, w, sg=seg):
        rows = v.reshape(-1, v.shape[-1])
        return cim.linear(rows, w, pt, g, sg, n_seg, dtype)

    inv, cs, scale = _yarn(cfg)
    pos = jnp.arange(t)
    a = p["attn"]
    xn = _rms(x, p["ln1"]["scale"], eps)
    q = proj(xn, a["wq"]).reshape(b, t, h, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, inv, cs)
    kv = proj(xn, a["wkv_a"]).reshape(b, t, r + rope)
    c_kv = _rms(kv[..., :r], a["kv_norm"]["scale"], eps)
    k_pe = _rope(kv[:, :, None, r:], pos, inv, cs)[:, :, 0]
    c_kv = c_kv.astype(kv_dtype).astype(dtype)
    k_pe = k_pe.astype(kv_dtype).astype(dtype)
    n_p = prompt_len
    seg2 = seg.reshape(b, t)
    kvb = proj(c_kv[:, :n_p], a["wkv_b"], seg2[:, :n_p].reshape(-1))
    kvb = kvb.reshape(b, n_p, h, nope + vd)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, :n_p, None, :], (b, n_p, h, rope))], -1)
    qp = jnp.concatenate([q_nope[:, :n_p], q_pe[:, :n_p]], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qp, k) * dtype(scale)
    sc = jnp.where(pos[:n_p, None] >= pos[None, :n_p], sc, -jnp.inf)
    outs = [jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1),
                       kvb[..., nope:]).reshape(b, n_p, h * vd)]
    w = a["wkv_b"]["w"].reshape(r, h, nope + vd)
    uk = dict(a["uk"], w=jnp.transpose(w[:, :, :nope], (1, 2, 0)))
    uv = dict(a["uv"], w=jnp.transpose(w[:, :, nope:], (1, 0, 2)))
    for q0 in range(n_p, t, POSITION_BLOCK):
        q1 = min(q0 + POSITION_BLOCK, t)
        n_q = q1 - q0
        sgq = seg2[:, q0:q1].reshape(-1)

        def per_head(wh, xh):
            return cim.linear(xh, wh, pt, g, sgq, n_seg, dtype)

        q_lat = jax.vmap(per_head, in_axes=(0, 1), out_axes=1)(
            uk, q_nope[:, q0:q1].reshape(b * n_q, h, nope))
        q_lat = q_lat.reshape(b, n_q, h, r)
        sc = (jnp.einsum("bqhc,bkc->bhqk", q_lat, c_kv)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, q0:q1], k_pe)) \
            * dtype(scale)
        sc = jnp.where(pos[q0:q1, None] >= pos[None, :], sc, -jnp.inf)
        ctx = jnp.einsum("bhqk,bkc->bqhc", jax.nn.softmax(sc, -1), c_kv)
        o = jax.vmap(per_head, in_axes=(0, 1), out_axes=1)(
            uv, ctx.reshape(b * n_q, h, r))
        outs.append(o.reshape(b, n_q, h * vd))
    return x + proj(jnp.concatenate(outs, 1), a["wo"]).reshape(b, t, d)


def _ffn_rows(cfg: dict, p, x, seg, n_seg, dense: bool, dtype):
    """x + FFN(x) over rows (M, d) in `n_seg` calls (`seg` (M,)): the
    dense SwiGLU, or the held experts (each over the rows routed to it)
    plus the shared experts."""
    s, pt, g = _sizes(cfg), point(cfg), cfg["max_gamma"]

    def proj(v, w, sg, ns):
        return cim.linear(v, w, pt, g, sg, ns, dtype)

    def mlp(v, w, sg=seg, ns=n_seg):
        hid = jax.nn.silu(proj(v, w["w_gate"], sg, ns)) \
            * proj(v, w["w_up"], sg, ns)
        return proj(hid, w["w_down"], sg, ns)

    xn = _rms(x, p["ln2"]["scale"], cfg["norm_eps"])
    if dense:
        return x + mlp(xn, p["mlp"])
    mo = p["moe"]
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(xn.astype(jnp.float32) @ mo["router"], -1)
    top_p, top_idx = jax.lax.top_k(probs, s["k"])
    if cfg["model"]["moe_norm_topk"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * cfg["model"]["moe_routed_scale"]
    first = cfg["model"]["moe_share"] * s["held"]
    out = mlp(xn, mo["shared"])
    for e in range(s["held"]):
        hit = top_idx == first + e
        routed = jnp.any(hit, -1)
        weight = jnp.sum(jnp.where(hit, top_p, 0.0), -1).astype(dtype)
        ew = jax.tree.map(lambda v: v[e], {n: mo[n] for n in
                                           ("w_gate", "w_up", "w_down")})
        y = mlp(xn, ew, jnp.where(routed, seg, n_seg), n_seg + 1)
        out = out + jnp.where(routed[:, None], weight[:, None] * y, 0)
    return x + out


# one compiled reference piece per (configuration, piece, shape, dtype)
_JITS: dict = {}


def _jit(cfg: dict, name: str, *key):
    k = (json.dumps(cfg, sort_keys=True), name) + key
    if k not in _JITS:
        dtype = jnp.dtype(key[-1]).type
        if name == "attention":
            n_seg, prompt_len, _ = key
            _JITS[k] = jax.jit(lambda layers, i, x, s: _attention(
                cfg, jax.tree.map(lambda a: a[i], layers), x, s, n_seg,
                prompt_len, dtype))
        else:
            n_seg, dense, _ = key
            _JITS[k] = jax.jit(lambda layers, i, x, s: _ffn_rows(
                cfg, jax.tree.map(lambda a: a[i], layers), x, s, n_seg,
                dense, dtype))
    return _JITS[k]


def hidden(cfg: dict, params, tokens: jnp.ndarray, prompt_len: int,
           dtype=jnp.float32) -> jnp.ndarray:
    """Normalized final hidden states (B, T - prompt_len + 1, d) at the
    positions whose logits choose a served token: the prompt's last, and
    each fed-back token's.  tokens = prompt ++ served tokens but the last.

    Rows quantize together as the program's calls do: all prompt rows of
    the batch in one segment, then one segment per decode position.  The
    FFN runs over the prompt's rows, then over blocks of POSITION_BLOCK
    positions: a block holds whole calls, so its ranges are the calls'."""
    b, t = tokens.shape
    seg = np.concatenate([np.zeros(prompt_len, np.int32),
                          np.arange(1, t - prompt_len + 1, dtype=np.int32)])
    n_seg = t - prompt_len + 1
    spans = [(0, prompt_len)] + [
        (q, min(q + POSITION_BLOCK, t))
        for q in range(prompt_len, t, POSITION_BLOCK)]
    dt = jnp.dtype(dtype).name
    with jax.default_matmul_precision(cfg["dot_precision"]):
        x = params["embed"][tokens].astype(dtype)
        for stack, dense in (("dense_layers", True), ("layers", False)):
            n = jax.tree.leaves(params[stack])[0].shape[0]
            for i in range(n):
                x = _jit(cfg, "attention", n_seg, prompt_len, dt)(
                    params[stack], jnp.int32(i), x,
                    jnp.asarray(np.tile(seg, b)))
                parts = []
                for q0, q1 in spans:
                    local = seg[q0:q1] - seg[q0]
                    ns = int(local[-1]) + 1
                    rows = x[:, q0:q1].reshape(b * (q1 - q0), -1)
                    y = _jit(cfg, "ffn", ns, dense, dt)(
                        params[stack], jnp.int32(i), rows,
                        jnp.asarray(np.tile(local, b)))
                    parts.append(y.reshape(b, q1 - q0, -1))
                x = jnp.concatenate(parts, 1)
        return _rms(x[:, prompt_len - 1:], params["final_norm"]["scale"],
                    cfg["norm_eps"])


def logits(cfg: dict, params, h: jnp.ndarray) -> jnp.ndarray:
    """The untied head over a block of final hidden states (..., d)."""
    with jax.default_matmul_precision(cfg["dot_precision"]):
        return h @ params["lm_head"]["w"].astype(h.dtype)
