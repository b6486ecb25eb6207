"""Plain float32 reference of DeepSeek-V2-Lite's forward pass.

Straightforward `jax.numpy` under `jax.default_matmul_precision("highest")`
with no cache, kernels or batching, following HF `modeling_deepseek`
(arXiv:2405.04434 §2): MLA with YaRN rope, a leading dense SwiGLU layer,
then MoE layers of greedy softmax top-k routed experts plus shared
experts, RMSNorm, an untied head.  Two modes:

* bypass (`cim` None or mode "bypass"): every projection a float matmul;
  attention is HF's, keys and values expanded through kv_b everywhere.
* CIM: every projection through the macro's digital arithmetic
  (core/digital_ref's ADC code and its dequantization, per row tile), with
  activation ranges taken per call as the program takes them: the
  `prompt_len` prompt rows of the batch are one call, every later position
  one call of its own; a grouped product's range is per head, an expert's
  per expert over the rows routed to it.

Departures from HF, each as the program makes it:

* the held share: only experts [share*held, (share+1)*held) are computed;
  routes to the others contribute nothing (`moe_held`, `moe_share`);
* the absorbed decode form: a position after the prompt attends through
  q_lat = q_nope . W_UK^T and (softmax . c_kv) . W_UV (models/mla.py), its
  two products CIM GEMMs of their own in CIM mode, over latents rounded to
  `cache_dtype`; the prompt's keys and values are expanded from the
  prompt's latents, rounded the same way;
* the router stays digital (float32 at HIGHEST), not a CIM projection.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import abn as abn_lib
from repro.core import digital_ref, mapping
from repro.core.cim_layers import CIMConfig
from repro.core.quantization import quantize_act, quantize_weight
from repro.models import mla


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def cim_linear(x: jnp.ndarray, p: Dict, cim: Optional[CIMConfig],
               seg: Optional[jnp.ndarray] = None, n_seg: int = 1
               ) -> jnp.ndarray:
    """x (M, K) @ p["w"] through the macro's digital arithmetic, one
    activation range per segment of rows (`seg` (M,) int32), one ADC
    conversion per even row tile; a float matmul in bypass mode."""
    if cim is None or cim.mode == "bypass":
        return x @ p["w"]
    k_dim = x.shape[1]
    aq = quantize_act(x, cim.r_in, segment_ids=seg, num_segments=n_seg)
    wq = quantize_weight(p["w"], cim.r_w, axis=0)
    gamma = abn_lib.abn_gamma(
        abn_lib.ABNParams(p["abn_log_gamma"], p["abn_beta"]),
        gamma_bits=cim.gamma_bits, max_gamma=cim.max_gamma)
    beta = p["abn_beta"]
    macro = cim.macro
    tiles = -(-k_dim // macro.n_rows)
    units = macro.units_for_rows(-(-k_dim // tiles))
    adc = dict(r_in=cim.r_in, r_w=cim.r_w, r_out=cim.r_out,
               n_dp=units * macro.rows_per_unit,
               swing=macro.swing_efficiency(units),
               alpha_adc=macro.alpha_adc())
    gain = gamma * digital_ref.adc_gain_factor(
        adc["r_in"], adc["r_w"], adc["r_out"], adc["n_dp"], adc["swing"],
        adc["alpha_adc"])
    zp = aq.zero / aq.scale
    acc = jnp.zeros((x.shape[0], p["w"].shape[1]), jnp.float32)
    for ks, ksz in mapping.split_k_slices(k_dim, tiles):
        wt = wq.q[ks:ks + ksz]
        dp = aq.q[:, ks:ks + ksz] @ wt
        beta_eff = beta + gain * (zp * jnp.sum(wt, 0))
        code = digital_ref.dsci_adc_code(dp, gamma=gamma,
                                         beta_codes=beta_eff, **adc)
        acc = acc + digital_ref.dequantize_code(code, gamma=gamma,
                                                beta_codes=beta, **adc)
    return acc * aq.scale * wq.scale


def _mlp(p, x, cim, seg, n_seg):
    g = cim_linear(x, p["w_gate"], cim, seg, n_seg)
    u = cim_linear(x, p["w_up"], cim, seg, n_seg)
    return cim_linear(jax.nn.silu(g) * u, p["w_down"], cim, seg, n_seg)


def _moe(cfg: ModelConfig, p, x, cim, seg, n_seg):
    """The held experts' part of the routed output, plus the shared
    experts once."""
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    top_p, top_idx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * cfg.moe_routed_scale
    out = jnp.zeros_like(x)
    for e in range(cfg.moe_held):
        hit = top_idx == cfg.moe_share * cfg.moe_held + e          # (M, k)
        routed = jnp.any(hit, -1)
        weight = jnp.sum(jnp.where(hit, top_p, 0.0), -1)
        # the expert's range spans only the rows routed to it
        e_seg = jnp.where(routed, seg, n_seg)
        ep = {name: jax.tree.map(lambda a: a[e], p[name])
              for name in ("w_gate", "w_up", "w_down")}
        y = _mlp(ep, x, cim, e_seg, n_seg + 1)
        out = out + jnp.where(routed[:, None], weight[:, None] * y, 0.0)
    if "shared" in p:
        out = out + _mlp(p["shared"], x, cim, seg, n_seg)
    return out


def _attention(cfg: ModelConfig, p, x, cim, seg, n_seg, b, t,
               prompt_len: int, cache_dtype):
    """MLA over (B*T, d) rows, b-major: the prompt positions expanded
    among themselves, each later position absorbed over every latent up
    to it (module docstring)."""
    h, r, nope, rope, vd = (cfg.n_heads, cfg.kv_lora_rank,
                            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.v_head_dim)
    pos = jnp.arange(t)
    q = cim_linear(x, p["wq"], cim, seg, n_seg).reshape(b, t, h, nope + rope)
    q_nope = q[..., :nope]
    q_pe = mla.rope_pairs(q[..., nope:], pos, cfg)
    kv = cim_linear(x, p["wkv_a"], cim, seg, n_seg).reshape(b, t, r + rope)
    c_kv = _rms(kv[..., :r], p["kv_norm"]["scale"])
    k_pe = mla.rope_pairs(kv[..., None, r:], pos, cfg)[:, :, 0]
    c_kv = c_kv.astype(cache_dtype).astype(jnp.float32)
    k_pe = k_pe.astype(cache_dtype).astype(jnp.float32)
    scale = mla.softmax_scale(cfg)
    n_p = prompt_len
    # the prompt: keys and values expanded from its own latents
    kvb = cim_linear(c_kv[:, :n_p].reshape(b * n_p, r), p["wkv_b"], cim,
                     seg.reshape(b, t)[:, :n_p].reshape(-1), n_seg)
    kvb = kvb.reshape(b, n_p, h, nope + vd)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        k_pe[:, :n_p, None, :], (b, n_p, h, rope))], -1)
    qp = jnp.concatenate([q_nope[:, :n_p], q_pe[:, :n_p]], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", qp, k) * scale
    s = jnp.where(pos[:n_p, None] >= pos[None, :n_p], s, -1e30)
    out_p = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                       kvb[..., nope:])
    outs = [out_p.reshape(b, n_p, h * vd)]
    if t > n_p:
        # later positions: the absorbed form, one call a position
        uk, uv = mla.absorbed_weights(p, cfg)
        dseg = seg.reshape(b, t)[:, n_p:].reshape(-1)
        qn = q_nope[:, n_p:].reshape(b * (t - n_p), h, nope)

        def per_head(w, xs):
            return cim_linear(xs, w, cim, dseg, n_seg)

        q_lat = jax.vmap(per_head, in_axes=(0, 1), out_axes=1)(uk, qn)
        q_lat = q_lat.reshape(b, t - n_p, h, r)
        s = (jnp.einsum("bqhc,bkc->bhqk", q_lat, c_kv)
             + jnp.einsum("bqhd,bkd->bhqk", q_pe[:, n_p:], k_pe)) * scale
        s = jnp.where(pos[n_p:, None] >= pos[None, :], s, -1e30)
        ctx = jnp.einsum("bhqk,bkc->bqhc", jax.nn.softmax(s, -1), c_kv)
        o = jax.vmap(per_head, in_axes=(0, 1), out_axes=1)(
            uv, ctx.reshape(b * (t - n_p), h, r))
        outs.append(o.reshape(b, t - n_p, h * vd))
    out = jnp.concatenate(outs, 1).reshape(b * t, h * vd)
    return cim_linear(out, p["wo"], cim, seg, n_seg)


def forward(cfg: ModelConfig, params, tokens: jnp.ndarray, *,
            cim: Optional[CIMConfig] = None,
            prompt_len: Optional[int] = None,
            cache_dtype=jnp.float32) -> jnp.ndarray:
    """Logits (B, T, V) of tokens (B, T) for the program's parameter tree
    (models/transformer.init_params).  tokens = a prompt of `prompt_len`
    ids (default T: all prompt) followed by the tokens fed back one a
    step; `cache_dtype` is the dtype the program's latent cache holds."""
    b, t = tokens.shape
    prompt_len = t if prompt_len is None else prompt_len
    step = np.concatenate([np.zeros(prompt_len, np.int32),
                           np.arange(1, t - prompt_len + 1, dtype=np.int32)])
    seg = jnp.asarray(np.tile(step, b))
    n_seg = t - prompt_len + 1
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32).reshape(b * t, -1)
        stacks = [(params["dense_layers"], True)] if cfg.dense_layers else []
        stacks.append((params["layers"], False))
        for stack, dense in stacks:
            n = jax.tree.leaves(stack)[0].shape[0]
            for i in range(n):
                p = jax.tree.map(lambda a: a[i], stack)
                h = _rms(x, p["ln1"]["scale"])
                x = x + _attention(cfg, p["attn"], h, cim, seg, n_seg, b, t,
                                   prompt_len, cache_dtype)
                h = _rms(x, p["ln2"]["scale"])
                x = x + (_mlp(p["mlp"], h, cim, seg, n_seg) if dense
                         else _moe(cfg, p["moe"], h, cim, seg, n_seg))
        x = _rms(x, params["final_norm"]["scale"])
        return (x @ params["lm_head"]["w"]).reshape(b, t, -1)
