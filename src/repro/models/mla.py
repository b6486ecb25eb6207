"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1) over a
latent decode cache.

Per token the layer caches the compressed latent c_kv (after its RMSNorm)
and the roped key k_pe shared by every head: kv_lora_rank + qk_rope_head_dim
values a layer, where per-head keys and values would take
n_heads * (qk_head_dim + v_head_dim).

* A call over several tokens (a prompt) expands its own latents through
  kv_b into per-head keys and values and attends within the call, as HF's
  `modeling_deepseek` does; with a cache the latents are first rounded to
  the cache dtype, so the prompt reads what decode will read.  A prompt is
  prefilled into an empty cache in one call.
* A one-token decode step uses the absorbed form and never expands the
  cache: W_UK is folded into the query and W_UV into the output,

      q_lat[h] = q_nope[h] . W_UK[h]^T                 (K 128, N 512)
      s[h, t]  = (q_lat[h] . c_kv[t] + q_pe[h] . k_pe[t]) * softmax_scale
      o[h]     = (softmax(s[h]) . c_kv) . W_UV[h]      (K 512, N 128)

  Both absorbed products are CIM GEMMs of one grouped call, the heads its
  groups (one activation range per head a call).  Their weights are the
  slices of kv_b's; their ABN gain and offset are their own (`uk`, `uv`).

RoPE is HF's for DeepSeek-V2: each rotated pair is (x[2i], x[2i+1]) and the
output is laid out evens first, odds second (HF de-interleaves before its
rotate_half); queries and keys share the layout, so scores are HF's.  YaRN
scales the frequencies and the softmax (`yarn_inv_freq`, `softmax_scale`).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cim_layers import (CIMConfig, analytic_log_gamma_init,
                                   cim_grouped_dense_apply,
                                   cim_linear_apply, init_cim_linear)
from repro.models.common import apply_norm, carried, init_norm


# ---------------------------------------------------------------------------
# YaRN rotary embedding (HF DeepseekV2YarnRotaryEmbedding)
# ---------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(c: ModelConfig) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (qk_rope/2,) float32, the cos/sin scale)."""
    dim, base = c.qk_rope_head_dim, c.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (base ** exps)
    if c.rope_yarn_factor <= 0:
        return extra.astype(np.float32), 1.0
    inter = 1.0 / (c.rope_yarn_factor * base ** exps)

    def corr_dim(rot):
        return (dim * math.log(c.rope_yarn_original / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(c.rope_yarn_beta_fast)), 0)
    high = min(math.ceil(corr_dim(c.rope_yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                       # 1: the unscaled frequency
    inv = inter * (1 - keep) + extra * keep
    m = (yarn_mscale(c.rope_yarn_factor, c.rope_yarn_mscale)
         / yarn_mscale(c.rope_yarn_factor, c.rope_yarn_mscale_all_dim))
    return inv.astype(np.float32), float(m)


def softmax_scale(c: ModelConfig) -> float:
    """(qk_nope + qk_rope)^-0.5, times mscale(factor, mscale_all_dim)^2
    under YaRN."""
    s = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    if c.rope_yarn_factor > 0 and c.rope_yarn_mscale_all_dim:
        m = yarn_mscale(c.rope_yarn_factor, c.rope_yarn_mscale_all_dim)
        s = s * m * m
    return s


def rope_pairs(x: jnp.ndarray, positions: jnp.ndarray, c: ModelConfig
               ) -> jnp.ndarray:
    """x (B, S, H, R) rotated pairwise at `positions` ((S,) or (B, S)):
    pair i is (x[2i], x[2i+1]); the output holds the rotated evens, then
    the rotated odds (HF's layout)."""
    inv, m = yarn_inv_freq(c)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * jnp.asarray(inv)
    cos = (jnp.cos(ang) * m)[:, :, None, :]
    sin = (jnp.sin(ang) * m)[:, :, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# parameters and cache
# ---------------------------------------------------------------------------

def _abn(groups: int, k: int, n: int, cim: Optional[CIMConfig]) -> Dict:
    lg = 0.0 if cim is None else analytic_log_gamma_init(k, cim)
    return {"abn_log_gamma": jnp.full((groups, n), lg, jnp.float32),
            "abn_beta": jnp.zeros((groups, n), jnp.float32)}


def init_mla(key: jax.Array, c: ModelConfig,
             cim: Optional[CIMConfig] = None) -> Dict:
    """q, kv_a, kv_b, o as CIM linears, the latent RMSNorm, and the ABN of
    the two absorbed products (`uk`, `uv`, one row per head)."""
    ks = jax.random.split(key, 4)
    h, r = c.n_heads, c.kv_lora_rank
    nope, rope, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    return {
        "wq": init_cim_linear(ks[0], c.d_model, h * (nope + rope), cfg=cim),
        "wkv_a": init_cim_linear(ks[1], c.d_model, r + rope, cfg=cim),
        "kv_norm": init_norm(r, "rmsnorm"),
        "wkv_b": init_cim_linear(ks[2], r, h * (nope + v), cfg=cim),
        "wo": init_cim_linear(ks[3], h * v, c.d_model, cfg=cim),
        "uk": _abn(h, nope, r, cim),
        "uv": _abn(h, r, v, cim),
    }


def absorbed_weights(p: Dict, c: ModelConfig) -> Tuple[Dict, Dict]:
    """The two absorbed products as grouped CIM params, their weights
    sliced from kv_b's: W_UK^T (H, qk_nope, rank), W_UV (H, rank, v)."""
    nope = c.qk_nope_head_dim
    w = p["wkv_b"]["w"].reshape(c.kv_lora_rank, c.n_heads,
                                nope + c.v_head_dim)
    uk = dict(p["uk"], w=jnp.transpose(w[:, :, :nope], (1, 2, 0)))
    uv = dict(p["uv"], w=jnp.transpose(w[:, :, nope:], (1, 0, 2)))
    return uk, uv


def init_latent_cache(n_layers: int, batch: int, max_len: int,
                      c: ModelConfig, dtype=jnp.bfloat16) -> Dict:
    """A layer stack's latent cache: c_kv (n_layers, B, L, rank), k_pe
    (n_layers, B, rope, L) and a shared write cursor a layer.  `max_len`
    holds every position.

    The layer scan carries the stack whole (layouts pinned, `carried`).
    c_kv is batch-major, each row's (L, rank) block the operand of the
    absorbed dots; k_pe holds its positions minor, the layout the TPU
    gives an (L, 64) block where the step takes it in — stored (L, rope),
    the pinned carry would differ from it and be converted at the step's
    entry and exit."""
    return {"c_kv": jnp.zeros((n_layers, batch, max_len, c.kv_lora_rank),
                              dtype),
            "k_pe": jnp.zeros((n_layers, batch, c.qk_rope_head_dim,
                               max_len), dtype),
            "idx": jnp.zeros((n_layers,), jnp.int32)}


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def _expanded(p: Dict, c: ModelConfig, cim: CIMConfig, q_nope, q_pe, c_kv,
              k_pe, positions) -> jnp.ndarray:
    """Attention of a call's tokens among themselves (causal), keys and
    values expanded from the call's latents through kv_b."""
    b, s, h, nope = q_nope.shape
    kv = cim_linear_apply(p["wkv_b"], c_kv, cim).reshape(
        b, s, h, nope + c.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q_nope, q_pe], -1).astype(jnp.float32)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (b, s, h, c.qk_rope_head_dim))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(jnp.float32))
    scores = scores * softmax_scale(c)
    pos = positions if positions.ndim == 1 else positions[0]
    keep = pos[:, None] >= pos[None, :]
    scores = jnp.where(keep[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q_nope.dtype).reshape(b, s, h * c.v_head_dim)


def _absorbed(p: Dict, c: ModelConfig, cim: CIMConfig, q_nope, q_pe,
              c_kv, k_pe, idx) -> jnp.ndarray:
    """One decode token per row against a layer's whole latent cache
    (c_kv (B, L, rank), k_pe (B, rope, L)), in the absorbed form (module
    docstring); slots at or past the cursor `idx` are masked."""
    b, _, h, _ = q_nope.shape
    dt = q_nope.dtype
    uk, uv = absorbed_weights(p, c)
    ckv = c_kv.astype(jnp.float32)
    kpe = k_pe.astype(jnp.float32)
    q_lat = cim_grouped_dense_apply(
        uk, jnp.swapaxes(q_nope[:, 0], 0, 1), cim)         # (H, B, rank)
    scores = (jnp.einsum("hbc,blc->bhl", q_lat.astype(jnp.float32), ckv)
              + jnp.einsum("bhr,brl->bhl", q_pe[:, 0].astype(jnp.float32),
                           kpe))
    scores = scores * softmax_scale(c)
    valid = jnp.arange(ckv.shape[1]) < idx
    scores = jnp.where(valid[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhl,blc->hbc", probs, ckv).astype(dt)
    o = cim_grouped_dense_apply(uv, ctx, cim)                # (H, B, v)
    return jnp.swapaxes(o, 0, 1).reshape(b, 1, h * c.v_head_dim)


def mla_block(p: Dict, x: jnp.ndarray, c: ModelConfig, cim: CIMConfig, *,
              positions: jnp.ndarray, cache: Optional[Dict] = None,
              layer=0) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x (B, S, d) -> (out (B, S, d), updated latent cache or None).

    `cache` is the whole layer stack's (init_latent_cache); `layer` (an int
    or a traced index) picks this layer's slice, written in place."""
    with jax.named_scope("lm.attention"):
        b, s, _ = x.shape
        h, r, nope = c.n_heads, c.kv_lora_rank, c.qk_nope_head_dim
        q = cim_linear_apply(p["wq"], x, cim).reshape(
            b, s, h, nope + c.qk_rope_head_dim)
        q_nope = q[..., :nope]
        q_pe = rope_pairs(q[..., nope:], positions, c)
        kv = cim_linear_apply(p["wkv_a"], x, cim)
        c_kv = apply_norm(p["kv_norm"], kv[..., :r], "rmsnorm")
        k_pe = rope_pairs(kv[..., None, r:], positions, c)[:, :, 0]
        new_cache = None
        if cache is not None:
            with jax.named_scope("lm.kv_write"):
                idx = cache["idx"][layer]
                new_cache = {
                    "c_kv": carried(jax.lax.dynamic_update_slice(
                        cache["c_kv"],
                        c_kv.astype(cache["c_kv"].dtype)[None],
                        (layer, 0, idx, 0))),
                    "k_pe": carried(jax.lax.dynamic_update_slice(
                        cache["k_pe"],
                        jnp.swapaxes(k_pe, 1, 2).astype(
                            cache["k_pe"].dtype)[None],
                        (layer, 0, 0, idx))),
                    "idx": cache["idx"].at[layer].add(s)}
        if cache is not None and s == 1:
            out = _absorbed(
                p, c, cim, q_nope, q_pe,
                jax.lax.dynamic_index_in_dim(new_cache["c_kv"], layer,
                                             keepdims=False),
                jax.lax.dynamic_index_in_dim(new_cache["k_pe"], layer,
                                             keepdims=False),
                idx + s)
        else:
            if cache is not None:
                # the prompt reads its latents as decode will: rounded to
                # the cache dtype
                c_kv = c_kv.astype(cache["c_kv"].dtype).astype(x.dtype)
                k_pe = k_pe.astype(cache["k_pe"].dtype).astype(x.dtype)
            out = _expanded(p, c, cim, q_nope, q_pe, c_kv, k_pe, positions)
        return cim_linear_apply(p["wo"], out, cim), new_cache
