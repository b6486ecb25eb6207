"""Model assembly for all assigned architectures.

One config-driven implementation covering:
  dense   : pre-norm decoder (GQA + gated MLP)      [minitron/qwen2/olmo/granite]
  moe     : dense attention + top-k expert FFN      [phi3.5-moe/mixtral]
  hybrid  : Griffin blocks (2x RG-LRU : 1x local attn)  [recurrentgemma]
  ssm     : Mamba-2 SSD stack                        [mamba2]
  vlm     : dense decoder + precomputed patch-embed prefix  [internvl2]
  audio   : Whisper enc-dec, conv frontend stubbed   [whisper]

Layer stacks are scanned (jax.lax.scan over stacked params) with optional
remat, so HLO size is depth-independent — required for the 80-layer dry-runs.
All projections run through the CIM layer (core/cim_layers.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.cim_layers import CIMConfig, cim_linear_apply, init_cim_linear
from repro.models import common as cm
from repro.models import mamba2 as m2
from repro.models import mla
from repro.models import rglru as rg
from repro.models.moe import (init_moe, init_moe_held, moe_block,
                              moe_held_block)
from repro.models.sharding import BATCH, TP, axis_size, shard


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _attn_cfg(cfg: ModelConfig, *, window: int = 0, causal: bool = True,
              use_rope: bool = True, n_heads: int = 0, n_kv: int = 0
              ) -> cm.AttnConfig:
    return cm.AttnConfig(
        d_model=cfg.d_model, n_heads=n_heads or cfg.n_heads,
        n_kv_heads=n_kv or cfg.n_kv_heads, head_dim=cfg.resolved_head_dim,
        qkv_bias=cfg.qkv_bias, window=window, causal=causal,
        rope_theta=cfg.rope_theta, use_rope=use_rope, impl=cfg.attn_impl)


# ---------------------------------------------------------------------------
# layer init (one layer; stacked via vmap over keys)
# ---------------------------------------------------------------------------

def _init_decoder_layer(cfg: ModelConfig, key: jax.Array,
                        dense: bool = False) -> Dict:
    """One decoder layer; `dense` gives a MoE stack's leading layers
    their dense FFN (width d_ff)."""
    ks = jax.random.split(key, 4)
    cim = cfg.cim
    p: Dict[str, Any] = {
        "ln1": cm.init_norm(cfg.d_model, cfg.norm_type),
        "ln2": cm.init_norm(cfg.d_model, cfg.norm_type),
        "attn": (mla.init_mla(ks[0], cfg, cim)
                 if cfg.kv_lora_rank else cm.init_attention(
                     ks[0], _attn_cfg(cfg, window=cfg.sliding_window), cim)),
    }
    if cfg.family == "moe" and not dense and cfg.moe_held:
        p["moe"] = init_moe_held(ks[1], cfg.d_model, cfg.moe_d_ff or cfg.d_ff,
                                 cfg.moe_experts, cfg.moe_held,
                                 cfg.moe_shared_d_ff, cim)
    elif cfg.family == "moe" and not dense:
        p["moe"] = init_moe(ks[1], cfg.d_model, cfg.moe_d_ff or cfg.d_ff,
                            cfg.moe_experts, cim)
    else:
        p["mlp"] = cm.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, cim)
    return p


def _init_ssm_layer(cfg: ModelConfig, key: jax.Array) -> Dict:
    return {
        "ln1": cm.init_norm(cfg.d_model, cfg.norm_type),
        "mixer": m2.init_mamba2_layer(
            key, cfg.d_model, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
            d_state=cfg.ssm_state, conv_width=cfg.conv_width, cim=cfg.cim),
    }


def _init_rec_layer(cfg: ModelConfig, key: jax.Array) -> Dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": cm.init_norm(cfg.d_model, cfg.norm_type),
        "ln2": cm.init_norm(cfg.d_model, cfg.norm_type),
        "rec": rg.init_rglru_block(ks[0], cfg.d_model,
                                   cfg.lru_width or cfg.d_model,
                                   cfg.conv_width, cfg.cim),
        "mlp": cm.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.cim),
    }


def _init_local_attn_layer(cfg: ModelConfig, key: jax.Array) -> Dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": cm.init_norm(cfg.d_model, cfg.norm_type),
        "ln2": cm.init_norm(cfg.d_model, cfg.norm_type),
        "attn": cm.init_attention(
            ks[0], _attn_cfg(cfg, window=cfg.local_window), cfg.cim),
        "mlp": cm.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.cim),
    }


def _init_enc_layer(cfg: ModelConfig, key: jax.Array) -> Dict:
    ks = jax.random.split(key, 2)
    return {
        "ln1": cm.init_norm(cfg.d_model, cfg.norm_type),
        "ln2": cm.init_norm(cfg.d_model, cfg.norm_type),
        "attn": cm.init_attention(
            ks[0], _attn_cfg(cfg, causal=False, use_rope=False), cfg.cim),
        "mlp": cm.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.cim),
    }


def _init_xdec_layer(cfg: ModelConfig, key: jax.Array) -> Dict:
    ks = jax.random.split(key, 3)
    return {
        "ln1": cm.init_norm(cfg.d_model, cfg.norm_type),
        "ln_x": cm.init_norm(cfg.d_model, cfg.norm_type),
        "ln2": cm.init_norm(cfg.d_model, cfg.norm_type),
        "attn": cm.init_attention(
            ks[0], _attn_cfg(cfg, use_rope=False), cfg.cim),
        "xattn": cm.init_attention(
            ks[1], _attn_cfg(cfg, causal=False, use_rope=False), cfg.cim),
        "mlp": cm.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.cim),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict:
    """Init the full parameter pytree for `cfg` (embeddings, every block
    of the family's layer stack, final norm, untied lm_head if any)."""
    keys = jax.random.split(key, 8)
    d = cfg.d_model
    emb_scale = d ** -0.5
    params: Dict[str, Any] = {
        "embed": emb_scale * jax.random.normal(
            keys[0], (cfg.vocab_size, d), jnp.float32),
        "final_norm": cm.init_norm(d, cfg.norm_type),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_cim_linear(keys[1], d, cfg.vocab_size)

    if cfg.family in ("dense", "moe", "vlm"):
        lk = jax.random.split(keys[2], cfg.n_layers - cfg.dense_layers)
        params["layers"] = jax.vmap(
            functools.partial(_init_decoder_layer, cfg))(lk)
        if cfg.dense_layers:
            dk = jax.random.split(keys[5], cfg.dense_layers)
            params["dense_layers"] = jax.vmap(functools.partial(
                _init_decoder_layer, cfg, dense=True))(dk)
    elif cfg.family == "ssm":
        lk = jax.random.split(keys[2], cfg.n_layers)
        params["layers"] = jax.vmap(
            functools.partial(_init_ssm_layer, cfg))(lk)
    elif cfg.family == "hybrid":
        nb, tail = divmod(cfg.n_layers, 3)
        bk = jax.random.split(keys[2], nb)

        def init_block(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return {"rec1": _init_rec_layer(cfg, k1),
                    "rec2": _init_rec_layer(cfg, k2),
                    "attn": _init_local_attn_layer(cfg, k3)}

        params["blocks"] = jax.vmap(init_block)(bk)
        if tail:
            tk = jax.random.split(keys[3], tail)
            params["tail"] = jax.vmap(
                functools.partial(_init_rec_layer, cfg))(tk)
    elif cfg.family == "audio":
        ek = jax.random.split(keys[2], cfg.encoder_layers)
        dk = jax.random.split(keys[3], cfg.n_layers)
        params["enc_layers"] = jax.vmap(
            functools.partial(_init_enc_layer, cfg))(ek)
        params["layers"] = jax.vmap(
            functools.partial(_init_xdec_layer, cfg))(dk)
        params["enc_norm"] = cm.init_norm(d, cfg.norm_type)
        params["pos_dec"] = 0.01 * jax.random.normal(
            keys[4], (cfg.max_target_len, d), jnp.float32)
    else:
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _decoder_layer(cfg: ModelConfig, p: Dict, x: jnp.ndarray, *,
                   positions: jnp.ndarray, cache: Optional[Dict], layer,
                   key: Optional[jax.Array] = None, dense: bool = False
                   ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """One pre-norm decoder layer (attention + MLP-or-MoE FFN); `cache` is
    the whole stack's, `layer` this layer's index in it.  `key` seeds the
    CIM noise model of the layer's projections (distinct folds for the
    attention and FFN banks).  `dense` marks a MoE stack's leading dense
    layer."""
    cim = cfg.cim
    k_attn = k_ffn = None
    if key is not None:
        k_attn, k_ffn = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)
    h = cm.apply_norm(p["ln1"], x, cfg.norm_type)
    kv = None if cache is None else cache["kv"]
    if cfg.kv_lora_rank:
        attn_out, new_kv = mla.mla_block(
            p["attn"], h, cfg, cim, positions=positions,
            cache=kv, layer=layer)
    else:
        attn_out, new_kv = cm.attention_block(
            p["attn"], h, _attn_cfg(cfg, window=cfg.sliding_window), cim,
            positions=positions, cache=kv, layer=layer, key=k_attn)
    x = x + attn_out
    h = cm.apply_norm(p["ln2"], x, cfg.norm_type)
    if cfg.family == "moe" and not dense and cfg.moe_held:
        ffn_out = moe_held_block(
            p["moe"], h, n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
            held=cfg.moe_held, share=cfg.moe_share,
            norm_topk=cfg.moe_norm_topk, routed_scale=cfg.moe_routed_scale,
            cim=cim, act=cfg.mlp_act)
        aux = 0.0
    elif cfg.family == "moe" and not dense:
        ffn_out, aux = moe_block(
            p["moe"], h, n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor, cim=cim, act=cfg.mlp_act,
            key=k_ffn)
    else:
        ffn_out = cm.mlp_block(p["mlp"], h, cim, cfg.mlp_act, key=k_ffn)
        aux = 0.0
    x = x + ffn_out
    new_cache = None if cache is None else {"kv": new_kv}
    return x, new_cache, jnp.asarray(aux, jnp.float32)


def _ssm_layer(cfg: ModelConfig, p: Dict, x, *, positions, cache, layer,
               key: Optional[jax.Array] = None):
    h = cm.apply_norm(p["ln1"], x, cfg.norm_type)

    def mixer(state):
        return m2.mamba2_layer(p["mixer"], h, cfg, cfg.cim, state=state)

    out, new_state = cm.at_layer(None if cache is None else cache["ssm"],
                                 layer, mixer)
    new_cache = None if cache is None else {"ssm": new_state}
    return x + out, new_cache, jnp.float32(0.0)


def _rec_layer(cfg: ModelConfig, p: Dict, x, *, cache, layer):
    h = cm.apply_norm(p["ln1"], x, cfg.norm_type)

    def rec(state):
        return rg.rglru_block(p["rec"], h, cfg.cim, state=state)

    out, new_state = cm.at_layer(None if cache is None else cache["rec"],
                                 layer, rec)
    x = x + out
    h = cm.apply_norm(p["ln2"], x, cfg.norm_type)
    x = x + cm.mlp_block(p["mlp"], h, cfg.cim, cfg.mlp_act)
    return x, (None if cache is None else {"rec": new_state})


def _local_attn_layer(cfg: ModelConfig, p: Dict, x, *, positions, cache,
                      layer):
    h = cm.apply_norm(p["ln1"], x, cfg.norm_type)
    out, new_kv = cm.attention_block(
        p["attn"], h, _attn_cfg(cfg, window=cfg.local_window), cfg.cim,
        positions=positions, cache=None if cache is None else cache["kv"],
        layer=layer)
    x = x + out
    h = cm.apply_norm(p["ln2"], x, cfg.norm_type)
    x = x + cm.mlp_block(p["mlp"], h, cfg.cim, cfg.mlp_act)
    return x, (None if cache is None else {"kv": new_kv})


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

def _scan_stack(layer_fn, stacked_params, x, cache, remat: bool,
                policy: str = "full", first: int = 0):
    """lax.scan over stacked layer params.  The stacked cache (or None)
    rides in the carry, never as xs/ys: `layer_fn(p, x, cache, i)` gets it
    whole with the layer's index `i` in it (the scan's layers are cache
    layers first, first + 1, ...) and returns it updated, so each layer's
    write lands in place and no per-layer slice of the cache is
    restacked."""
    if remat and policy == "dots":
        fn = jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat:
        fn = jax.checkpoint(layer_fn)
    else:
        fn = layer_fn
    n_layers = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def body(carry, xs):
        x, aux, c = carry
        p, i = xs
        with jax.named_scope("lm.layer"):
            new_x, new_c, a = fn(p, x, c, i)
            return (new_x.astype(x.dtype), aux + a, new_c), None

    (x, aux, cache), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0), cache),
        (stacked_params, jnp.arange(first, first + n_layers,
                                    dtype=jnp.int32)))
    return x, cache, aux


def _decoder_stack(cfg: ModelConfig, params, x, positions, cache, key=None):
    layer = {"dense": _decoder_layer, "moe": _decoder_layer,
             "vlm": _decoder_layer, "ssm": _ssm_layer}[cfg.family]

    def stack(p, x, c, dense=False, first=0):
        def f(p, x, c, i):
            # a noise-keyed run folds a distinct key per layer index (the
            # scan body sees a traced index, so one trace covers every
            # layer)
            k = None if key is None else jax.random.fold_in(key, i)
            kw = {"dense": True} if dense else {}
            return layer(cfg, p, x, positions=positions, cache=c, layer=i,
                         key=k, **kw)

        return _scan_stack(f, p, x, c, cfg.remat, cfg.remat_policy, first)

    if cfg.dense_layers:
        # the leading dense layers, then the scanned MoE stack; both scans
        # carry the one cache stack of every layer, the MoE layers after
        # the dense ones
        x, cache, aux0 = stack(params["dense_layers"], x, cache, dense=True)
        x, cache, aux = stack(params["layers"], x, cache,
                              first=cfg.dense_layers)
        return x, cache, aux0 + aux
    return stack(params["layers"], x, cache)


def _hybrid_stack(cfg: ModelConfig, params, x, positions, cache):
    def block_fn(p, x, c, i):
        c1 = None if c is None else c["rec1"]
        c2 = None if c is None else c["rec2"]
        c3 = None if c is None else c["attn"]
        x, nc1 = _rec_layer(cfg, p["rec1"], x, cache=c1, layer=i)
        x, nc2 = _rec_layer(cfg, p["rec2"], x, cache=c2, layer=i)
        x, nc3 = _local_attn_layer(cfg, p["attn"], x,
                                   positions=positions, cache=c3, layer=i)
        nc = None if c is None else {"rec1": nc1, "rec2": nc2, "attn": nc3}
        return x, nc, jnp.float32(0.0)

    bc = None if cache is None else cache["blocks"]
    x, new_bc, aux = _scan_stack(block_fn, params["blocks"], x, bc, cfg.remat)

    new_tail = None
    if "tail" in params:
        def tail_fn(p, x, c, i):
            x, nc = _rec_layer(cfg, p, x, cache=c, layer=i)
            return x, nc, jnp.float32(0.0)
        tc = None if cache is None else cache["tail"]
        x, new_tail, _ = _scan_stack(tail_fn, params["tail"], x, tc, cfg.remat)

    new_cache = None if cache is None else {"blocks": new_bc, "tail": new_tail}
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# public forward passes
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token-id lookup into the (sharded) embedding table, cast to the
    model compute dtype."""
    with jax.named_scope("lm.embed"):
        emb = shard(params["embed"], TP, None)
        x = emb[tokens].astype(_dtype(cfg))
        return shard(x, BATCH, None, None)


def lm_logits(cfg: ModelConfig, params, x: jnp.ndarray) -> jnp.ndarray:
    """Final norm + LM head (tied embedding, bypass-mode lm_head, or
    deploy-quantized serving weights — always digital, see DESIGN.md)."""
    x = cm.apply_norm(params["final_norm"], x, cfg.norm_type)
    with jax.named_scope("lm.head"):
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T.astype(x.dtype)
        elif "w" in params["lm_head"]:
            # lm_head stays in bypass mode (DESIGN.md: quality-critical)
            logits = x @ params["lm_head"]["w"].astype(x.dtype)
        else:   # deploy-quantized serving weights
            head = params["lm_head"]
            logits = x @ (head["w_q"].astype(x.dtype)
                          * head["w_scale"].astype(x.dtype))
        return shard(logits, BATCH, None, TP)


def forward(cfg: ModelConfig, params, tokens: jnp.ndarray, *,
            positions: Optional[jnp.ndarray] = None,
            cache: Optional[Dict] = None,
            prefix_embeds: Optional[jnp.ndarray] = None,
            encoder_frames: Optional[jnp.ndarray] = None,
            key: Optional[jax.Array] = None
            ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """Returns (logits, new_cache, aux_loss).

    tokens (B, S); positions default arange (no cache) / cache index offset.
    vlm: prefix_embeds (B, P, D) prepended.  audio: encoder_frames (B,T,D)
    run through the encoder (train/prefill) — for cached decode the cross
    KV lives in the cache instead.  `key` seeds the CIM noise model of the
    projections (decoder-stack families only; one fold per layer).
    """
    if key is not None and cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"noise-keyed forward is not wired for family {cfg.family!r}")
    if key is not None and (cfg.kv_lora_rank or cfg.moe_held
                            or cfg.dense_layers):
        raise ValueError("noise-keyed forward is not wired for latent "
                         "attention, held-expert or dense-prefix stacks")
    b, s = tokens.shape
    x = embed_tokens(cfg, params, tokens)

    if cfg.family == "vlm" and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        s = x.shape[1]

    inner_cache = None if cache is None else cache["layers"]
    if positions is None:
        if cache is not None:
            # (S,) from a shared position; (B, S) from a slot-mapped
            # cache's per-slot positions
            positions = cache["pos"][..., None] + jnp.arange(s)
        else:
            positions = jnp.arange(s)

    if cfg.family == "audio":
        logits, new_inner, aux = _audio_forward(
            cfg, params, x, positions, inner_cache, encoder_frames)
    elif cfg.family == "hybrid":
        x, new_inner, aux = _hybrid_stack(cfg, params, x, positions,
                                          inner_cache)
        logits = lm_logits(cfg, params, x)
    else:
        x, new_inner, aux = _decoder_stack(cfg, params, x, positions,
                                           inner_cache, key=key)
        logits = lm_logits(cfg, params, x)
    new_cache = (None if cache is None
                 else {"pos": cache["pos"] + s, "layers": new_inner})
    return logits, new_cache, aux


def _audio_forward(cfg, params, x, positions, cache, encoder_frames):
    """Whisper backbone.  Modes:
       * train / prefill : encoder_frames given — run the encoder, compute
         fresh cross K/V (stored into the cache if one is passed);
       * cached decode   : encoder_frames None — use cache[...]["xkv"]."""
    pos_emb = params["pos_dec"]
    pos = jnp.clip(positions, 0, cfg.max_target_len - 1)
    x = x + pos_emb[pos].astype(x.dtype)

    enc = None
    if encoder_frames is not None:
        enc = encoder_frames.astype(x.dtype)
        enc = enc + _sinusoid(enc.shape[1], cfg.d_model).astype(x.dtype)
        enc_pos = jnp.arange(enc.shape[1])

        def enc_fn(p, h, c, i):
            hh = cm.apply_norm(p["ln1"], h, cfg.norm_type)
            out, _ = cm.attention_block(
                p["attn"], hh, _attn_cfg(cfg, causal=False, use_rope=False),
                cfg.cim, positions=enc_pos)
            h = h + out
            hh = cm.apply_norm(p["ln2"], h, cfg.norm_type)
            h = h + cm.mlp_block(p["mlp"], hh, cfg.cim, cfg.mlp_act)
            return h, None, jnp.float32(0.0)

        enc, _, _ = _scan_stack(enc_fn, params["enc_layers"], enc, None,
                                cfg.remat)
        enc = cm.apply_norm(params["enc_norm"], enc, cfg.norm_type)

    def dec_fn(p, h, c, i):
        hh = cm.apply_norm(p["ln1"], h, cfg.norm_type)
        out, nkv = cm.attention_block(
            p["attn"], hh, _attn_cfg(cfg, use_rope=False), cfg.cim,
            positions=positions, cache=None if c is None else c["kv"],
            layer=i)
        h = h + out
        hh = cm.apply_norm(p["ln_x"], h, cfg.norm_type)

        def xattn(xkv):
            # cross K/V: fresh from the encoder (train/prefill), else the
            # layer's cached slice
            return cm.attention_block(
                p["xattn"], hh, _attn_cfg(cfg, causal=False, use_rope=False),
                cfg.cim, positions=positions, x_kv=enc,
                cross_kv=None if enc is not None else xkv,
                cache={} if xkv is not None else None)

        out, nxkv = cm.at_layer(None if c is None else c["xkv"], i, xattn)
        h = h + out
        hh = cm.apply_norm(p["ln2"], h, cfg.norm_type)
        h = h + cm.mlp_block(p["mlp"], hh, cfg.cim, cfg.mlp_act)
        nc = None if c is None else {"kv": nkv, "xkv": nxkv}
        return h, nc, jnp.float32(0.0)

    x, new_dec, _ = _scan_stack(dec_fn, params["layers"], x, cache,
                                cfg.remat)
    return lm_logits(cfg, params, x), new_dec, jnp.float32(0.0)


def _sinusoid(length: int, channels: int) -> jnp.ndarray:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(channels // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-dim * (9.21 / (channels // 2 - 1)))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=1)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def _kv_cache_len(cfg: ModelConfig, max_len: int, window: int) -> int:
    if window > 0:
        return min(max_len, window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict:
    """Decode cache pytree: {"pos": scalar, "layers": stacked per-layer},
    attention caches in the axis order of cm.init_kv_cache and
    mla.init_latent_cache; the layer scan carries each stack whole."""
    hd = cfg.resolved_head_dim if cfg.n_heads else 0
    g = cfg.n_kv_heads

    def stack(tree, n):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n,) + a.shape), tree)

    def kv(n, length):
        return cm.init_kv_cache(n, batch, length, g, hd, dtype)

    pos = jnp.array(0, jnp.int32)
    if cfg.family in ("dense", "moe", "vlm") and cfg.kv_lora_rank:
        # latent attention: c_kv and k_pe a token a layer, never per head
        return {"pos": pos, "layers": {"kv": mla.init_latent_cache(
            cfg.n_layers, batch, max_len, cfg, dtype)}}
    if cfg.family in ("dense", "moe", "vlm"):
        length = _kv_cache_len(cfg, max_len, cfg.sliding_window)
        return {"pos": pos, "layers": {"kv": kv(cfg.n_layers, length)}}
    if cfg.family == "ssm":
        st = m2.init_mamba2_state(batch, cfg.d_model, cfg)
        return {"pos": pos,
                "layers": {"ssm": stack(st, cfg.n_layers)}}
    if cfg.family == "hybrid":
        nb, tail = divmod(cfg.n_layers, 3)
        width = cfg.lru_width or cfg.d_model
        rec = rg.init_rglru_state(batch, width, cfg.conv_width)
        blocks = {"rec1": {"rec": stack(rec, nb)},
                  "rec2": {"rec": stack(rec, nb)},
                  "attn": {"kv": kv(nb, _kv_cache_len(cfg, max_len,
                                                      cfg.local_window))}}
        layers = {"blocks": blocks, "tail": None}
        if tail:
            layers["tail"] = {"rec": stack(rec, tail)}
        return {"pos": pos, "layers": layers}
    if cfg.family == "audio":
        xkv = stack({"k": jnp.zeros((batch, max_len, g, hd), dtype),
                     "v": jnp.zeros((batch, max_len, g, hd), dtype)},
                    cfg.n_layers)
        dec = {"kv": kv(cfg.n_layers, cfg.max_target_len), "xkv": xkv}
        return {"pos": pos, "layers": dec}
    raise ValueError(cfg.family)


def init_slot_cache(cfg: ModelConfig, slots: int, max_len: int,
                    dtype=jnp.bfloat16) -> Dict:
    """Slot-mapped decode cache for in-flight (continuous) batching:
    {"pos": (slots,) per-slot position, "layers": {"kv":
    cm.init_slot_kv_cache}} — every slot rides its own ring cursor, so
    requests at different sequence offsets decode fused in one batch.
    Attention-cache families only (dense/moe/vlm)."""
    if cfg.family not in ("dense", "moe", "vlm") or cfg.kv_lora_rank \
            or cfg.dense_layers:
        raise ValueError(
            f"slot-mapped decode supports the per-head KV caches of "
            f"dense/moe/vlm stacks, not {cfg.name!r}")
    length = _kv_cache_len(cfg, max_len, cfg.sliding_window)
    kvs = cm.init_slot_kv_cache(cfg.n_layers, slots, length, cfg.n_kv_heads,
                                cfg.resolved_head_dim, dtype)
    return {"pos": jnp.zeros((slots,), jnp.int32), "layers": {"kv": kvs}}


def write_slot_cache(cache: Dict, slot: int, prefill: Dict) -> Dict:
    """Admit a prefilled request into slot `slot` of a slot-mapped cache:
    scatter the batch-1 `prefill` cache's K/V rings, per-layer cursors and
    position into the slot (gather-free; every other slot untouched)."""
    kv = cm.write_slot_kv(cache["layers"]["kv"], slot,
                          prefill["layers"]["kv"])
    return {"pos": cache["pos"].at[slot].set(prefill["pos"]),
            "layers": {"kv": kv}}


def free_slot_cache(cache: Dict, slot: int) -> Dict:
    """Retire the request in slot `slot`: reset its cursors/position only
    (its K/V rows stay in place until the next admission overwrites them —
    per-row masks keep dead rows invisible to everyone else)."""
    return {"pos": cache["pos"].at[slot].set(0),
            "layers": {"kv": cm.free_slot_kv(cache["layers"]["kv"], slot)}}
