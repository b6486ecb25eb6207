"""Shared building blocks for the model zoo: norms, rotary, attention
(full / causal / sliding-window / cross, flash-style streaming for long
sequences), KV caches, and CIM-quantized projections.

Every weight-bearing projection goes through core.cim_layers.cim_linear_apply,
so the paper's technique (fakequant with ABN reshaping) is a config flag away
for every architecture.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.core.cim_layers import CIMConfig, cim_linear_apply, init_cim_linear
from repro.models.sharding import BATCH, TP, shard


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(d: int, kind: str) -> Dict:
    """Parameters for a `kind` norm over a width-`d` feature axis
    (rmsnorm / layernorm / OLMo-style non-parametric layernorm)."""
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    if kind == "nonparam_ln":          # OLMo: non-parametric LayerNorm
        return {}
    raise ValueError(kind)


def apply_norm(params: Dict, x: jnp.ndarray, kind: str,
               eps: float = 1e-6) -> jnp.ndarray:
    """Normalize the trailing feature axis in float32, cast back to
    x.dtype.  `kind` matches init_norm."""
    with jax.named_scope("lm.norm"):
        xf = x.astype(jnp.float32)
        if kind == "rmsnorm":
            y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                   + eps)
            y = y * params["scale"]
        elif kind in ("layernorm", "nonparam_ln"):
            mu = jnp.mean(xf, -1, keepdims=True)
            var = jnp.var(xf, -1, keepdims=True)
            y = (xf - mu) * jax.lax.rsqrt(var + eps)
            if kind == "layernorm":
                y = y * params["scale"] + params["bias"]
        else:
            raise ValueError(kind)
        return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse rotary frequencies, shape (head_dim // 2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               inv_freq: jnp.ndarray) -> jnp.ndarray:
    """x: (B, S, H, D); positions: (B, S) or (S,)."""
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B,S,D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _mask_value(dtype):
    return jnp.finfo(dtype).min


def attention_scores_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, *,
                          causal: bool, window: int) -> jnp.ndarray:
    """(..., Sq, Sk) boolean keep-mask."""
    rel = q_pos[:, None] - k_pos[None, :]
    keep = (k_pos >= 0)[None, :] & (rel >= 0) if causal else \
        jnp.broadcast_to((k_pos >= 0)[None, :], rel.shape)
    if window > 0:
        keep = keep & (rel < window)
    return keep


def plain_attention(q, k, v, *, q_pos, k_pos, causal, window=0):
    """Reference attention; q (B,Sq,H,D), k/v (B,Sk,G,D).

    q_pos/k_pos are (Sq,)/(Sk,) shared across the batch, or (B,Sq)/(B,Sk)
    for per-row positions (slot-mapped in-flight decode, where every batch
    row sits at its own sequence offset) — the keep-mask is then built per
    batch row."""
    b, sq, h, d = q.shape
    g = k.shape[2]
    rep = h // g
    qf = q.astype(jnp.float32) / (d ** 0.5)
    qf = qf.reshape(b, sq, g, rep, d)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qf, k.astype(jnp.float32))
    if q_pos.ndim == 2 or k_pos.ndim == 2:
        qp = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(
            q_pos[None], (b, sq))
        kp = k_pos if k_pos.ndim == 2 else jnp.broadcast_to(
            k_pos[None], (b, k.shape[1]))
        keep = jax.vmap(functools.partial(
            attention_scores_mask, causal=causal, window=window))(qp, kp)
        scores = jnp.where(keep[:, None, None], scores, -1e30)
    else:
        keep = attention_scores_mask(q_pos, k_pos, causal=causal,
                                     window=window)
        scores = jnp.where(keep[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def flash_attention(q, k, v, *, q_pos, k_pos, causal, window=0,
                    kv_block: int = 1024):
    """Streaming (online-softmax) attention: O(Sq * kv_block) live memory.

    Used whenever Sk is large (long-context prefill / whisper encoder).
    Shapes as plain_attention.  Pure lax.scan: HLO size O(1) in Sk.
    """
    b, sq, h, d = q.shape
    sk, g = k.shape[1], k.shape[2]
    rep = h // g
    if sk % kv_block:
        pad = kv_block - sk % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=-10**9)
        sk += pad
    n_blk = sk // kv_block
    kb = k.reshape(b, n_blk, kv_block, g, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blk, kv_block, g, d).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(n_blk, kv_block)
    qf = (q.astype(jnp.float32) / (d ** 0.5)).reshape(b, sq, g, rep, d)

    def step(carry, blk):
        acc, m, l = carry
        kc, vc, pc = blk
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kc.astype(jnp.float32))
        keep = attention_scores_mask(q_pos, pc, causal=causal, window=window)
        s = jnp.where(keep[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, -1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p, vc.astype(jnp.float32))
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, g, rep, sq, d), jnp.float32)
    m0 = jnp.full((b, g, rep, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, g, rep, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kb, vb, pb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (GQA, optional bias / SWA / cross), CIM projections
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    """Static attention-block hyperparameters (GQA shape, RoPE, window,
    flash threshold, kernel implementation)."""
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    window: int = 0                    # >0: sliding-window attention
    causal: bool = True
    rope_theta: float = 1e6
    use_rope: bool = True
    flash_threshold: int = 8192        # Sk above which the streaming path is used
    impl: str = "jnp"                  # jnp | pallas (fused VMEM kernel)


def init_attention(key: jax.Array, cfg: AttnConfig,
                   cim: Optional[CIMConfig] = None) -> Dict:
    """Q/K/V/O projection params (CIM-linear layout) + optional biases."""
    ks = jax.random.split(key, 4)
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": init_cim_linear(ks[0], d, h * hd, cfg=cim),
        "wk": init_cim_linear(ks[1], d, g * hd, cfg=cim),
        "wv": init_cim_linear(ks[2], d, g * hd, cfg=cim),
        "wo": init_cim_linear(ks[3], h * hd, d, cfg=cim),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), jnp.float32)
        p["bk"] = jnp.zeros((g * hd,), jnp.float32)
        p["bv"] = jnp.zeros((g * hd,), jnp.float32)
    return p


def _repeat_kv_to(x: jnp.ndarray, target_heads: int) -> jnp.ndarray:
    """Repeat KV heads so the head axis is TP-shardable (DESIGN.md §5)."""
    g = x.shape[2]
    if g >= target_heads:
        return x
    return jnp.repeat(x, target_heads // g, axis=2)


def attention_block(params: Dict, x: jnp.ndarray, cfg: AttnConfig,
                    cim: CIMConfig, *, positions: jnp.ndarray,
                    cache: Optional[Dict] = None,
                    layer=0,
                    kv_repeat_to: int = 0,
                    x_kv: Optional[jnp.ndarray] = None,
                    cross_kv: Optional[Dict] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    key: Optional[jax.Array] = None
                    ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Self- (x_kv None) or cross- (x_kv given) attention with optional
    KV cache for decode.  `cross_kv` supplies precomputed cross-attention
    K/V ({"k","v"}) during cached decode.  Returns (out, updated_cache).

    A self-attention `cache` is the whole layer stack's (init_kv_cache);
    `layer` (an int or a traced index) picks this layer's slice, which is
    written in place and read back without a copy of the stack.

    `key` seeds the CIM noise model of the four projections (a distinct
    fold per projection); None keeps them clean/deterministic.

    The self-attention decode cache is a *ring buffer* of length L: writes
    land at idx % L, so sliding-window layers keep only their window."""
    with jax.named_scope("lm.attention"):
        b, s, d = x.shape
        h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        src = x if x_kv is None else x_kv
        kq = kk_key = kv_key = ko = None
        if key is not None:
            kq, kk_key, kv_key, ko = (jax.random.fold_in(key, i)
                                      for i in range(4))

        use_pallas = (cfg.impl == "pallas" and s > 1 and cache is None
                      and cross_kv is None)
        q = cim_linear_apply(params["wq"], x, cim, key=kq)
        if "bq" in params:
            q = q + params["bq"]
        q = q.reshape(b, s, h, hd)
        if not use_pallas:
            # pallas path: the kernel's shard_map in_specs define the layout;
            # an extra constraint here only inserts reshard copies
            q = shard(q, BATCH, None, TP, None)

        if cross_kv is not None:
            # cross-attention decode: encoder KV precomputed at prefill
            k, v = cross_kv["k"], cross_kv["v"]
            k_pos = jnp.arange(k.shape[1])
            new_cache = cross_kv
        else:
            kk = cim_linear_apply(params["wk"], src, cim, key=kk_key)
            vv = cim_linear_apply(params["wv"], src, cim, key=kv_key)
            if "bk" in params:
                kk, vv = kk + params["bk"], vv + params["bv"]
            k = kk.reshape(b, src.shape[1], g, hd)
            v = vv.reshape(b, src.shape[1], g, hd)
            src_pos = positions if x_kv is None else (
                kv_positions if kv_positions is not None
                else jnp.arange(src.shape[1]))
            if cfg.use_rope and x_kv is None:
                inv = rope_frequencies(hd, cfg.rope_theta)
                q = apply_rope(q, positions, inv)
                k = apply_rope(k, src_pos, inv)
            if kv_repeat_to:
                k = _repeat_kv_to(k, kv_repeat_to)
                v = _repeat_kv_to(v, kv_repeat_to)
            if use_pallas:
                pass  # shard_map in_specs drive k/v layout (replicated on TP)
            if cache is not None and x_kv is None:
                # decode: the stacked ring cache (n_layers, L, B, g, hd)
                # rides the layer scan's carry; this layer's s rows land at
                # (layer, idx % L) in place (s == 1 for decode; a prompt
                # prefilled into the cache needs idx + s <= L).  `idx` is
                # the layer's shared cursor, or a (B,) per-slot cursor of a
                # slot-mapped cache (in-flight batching), whose rows each
                # ride their own ring position and get per-row masks.
                length = cache["k"].shape[1]
                idx = cache["idx"][layer]
                with jax.named_scope("lm.kv_write"):
                    write = jax.lax.rem(idx, length)
                    kt = jnp.swapaxes(k, 0, 1).astype(cache["k"].dtype)
                    vt = jnp.swapaxes(v, 0, 1).astype(cache["v"].dtype)
                    if idx.ndim:
                        if s != 1:
                            raise ValueError(
                                f"slot-mapped KV decode is single-token "
                                f"(s=1), got s={s}; prefill per request and "
                                "scatter into the slot with write_slot_kv")
                        rows = jnp.arange(b)
                        k_all = cache["k"].at[layer, write, rows].set(kt[0])
                        v_all = cache["v"].at[layer, write, rows].set(vt[0])
                    else:
                        at = (layer, write, 0, 0, 0)
                        k_all = jax.lax.dynamic_update_slice(
                            cache["k"], kt[None], at)
                        v_all = jax.lax.dynamic_update_slice(
                            cache["v"], vt[None], at)
                    k_all = shard(k_all, None, TP, BATCH, None, None)
                    v_all = shard(v_all, None, TP, BATCH, None, None)
                    k_all, v_all = carried(k_all), carried(v_all)
                    new_cache = {"k": k_all, "v": v_all,
                                 "idx": cache["idx"].at[layer].add(s)}
                k = jnp.swapaxes(jax.lax.dynamic_index_in_dim(
                    k_all, layer, keepdims=False), 0, 1)
                v = jnp.swapaxes(jax.lax.dynamic_index_in_dim(
                    v_all, layer, keepdims=False), 0, 1)
                # position held by ring slot j after the write (per batch
                # row for a per-slot cursor)
                j = jnp.arange(length)
                last = (idx + s - 1)[..., None]
                src_pos = last - jnp.mod(last - j, length)
                src_pos = jnp.where(src_pos >= 0, src_pos, -10**9)
            elif cache is not None:
                new_cache = {"k": k, "v": v}
            else:
                new_cache = None
            if (cache is None or x_kv is not None) and not use_pallas:
                k = shard(k, BATCH, None, TP, None)
                v = shard(v, BATCH, None, TP, None)
            k_pos = src_pos

        # per-slot decode keeps 2D (B, S) q positions so the per-row masks of
        # plain_attention line up; otherwise 2D positions collapse to row 0
        # (shared across the batch, the pre-slot contract)
        per_row = getattr(k_pos, "ndim", 1) == 2
        q_pos = positions if (positions.ndim == 1 or per_row) else positions[0]
        if use_pallas:
            # fused VMEM flash kernel (fwd + bwd); positions are contiguous
            # 0..S-1 in the no-cache path, masks generated in-kernel
            from repro.kernels.flash_attn.ops import flash_attention_sharded
            out = flash_attention_sharded(
                q, k, v, cfg.causal and x_kv is None and s > 1,
                cfg.window if x_kv is None else 0)
        elif k.shape[1] > cfg.flash_threshold and s > 1:
            out = flash_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                  causal=cfg.causal and x_kv is None,
                                  window=cfg.window)
        else:
            out = plain_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                  causal=cfg.causal and x_kv is None and s > 1,
                                  window=cfg.window if x_kv is None else 0)
        out = out.reshape(b, s, h * hd)
        y = cim_linear_apply(params["wo"], out, cim, key=ko)
        return shard(y, BATCH, None, None), new_cache


def init_kv_cache(n_layers: int, batch: int, max_len: int, n_kv: int,
                  head_dim: int, dtype=jnp.bfloat16) -> Dict:
    """Ring-buffer decode cache of a layer stack with one shared write
    cursor a layer (all batch rows advance in lockstep — the classic
    static-batch serving shape).

    K and V are stored position-major, (n_layers, L, B, g, hd): a step's
    token of every row is one contiguous (B, g, hd) block, written in place
    into the stack the layer scan carries, and decode attention reads the
    layer's slice straight from it (layouts pinned, `carried`)."""
    shape = (n_layers, max_len, batch, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "idx": jnp.zeros((n_layers,), jnp.int32)}


def init_slot_kv_cache(n_layers: int, slots: int, max_len: int, n_kv: int,
                       head_dim: int, dtype=jnp.bfloat16) -> Dict:
    """Slot-mapped decode cache for in-flight (continuous) batching.

    Same K/V layout as init_kv_cache but `idx` is (n_layers, slots): a
    *per-slot* write cursor, so every slot rides its own ring position and
    requests at different sequence offsets decode fused in one batch.
    attention_block detects the vector cursor and switches to per-row
    scatter writes and per-row masks.  Admit a request with write_slot_kv
    (scatter its prefilled batch-1 cache into a slot), retire with
    free_slot_kv (cursor reset only — the stale K/V rows are never moved
    or gathered)."""
    shape = (n_layers, max_len, slots, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "idx": jnp.zeros((n_layers, slots), jnp.int32)}


def write_slot_kv(cache: Dict, slot, prefill: Dict) -> Dict:
    """Admit one request: scatter its prefilled batch-1 KV cache (an
    init_kv_cache the request was prefilled into) into `slot` of a
    slot-mapped cache and set the slot's cursors to the prefill length.
    Leaves every other slot untouched — admission never perturbs the
    requests already in flight."""
    return {"k": cache["k"].at[:, :, slot].set(
                prefill["k"][:, :, 0].astype(cache["k"].dtype)),
            "v": cache["v"].at[:, :, slot].set(
                prefill["v"][:, :, 0].astype(cache["v"].dtype)),
            "idx": cache["idx"].at[:, slot].set(prefill["idx"])}


def free_slot_kv(cache: Dict, slot) -> Dict:
    """Retire one request: reset the slot's write cursors to 0.

    Gather-free — the slot's stale K/V rows stay in place (a zero cursor
    masks every ring position out of the attention scores, and the next
    admit overwrites them), so retirement moves no cache data and cannot
    perturb the surviving requests."""
    return {"k": cache["k"], "v": cache["v"],
            "idx": cache["idx"].at[:, slot].set(0)}


def carried(stack: jnp.ndarray) -> jnp.ndarray:
    """Pin a carried cache buffer to its stored (row-major) layout.  Left
    free, XLA lays the layer scan's carry out as the attention of the
    moment prefers — which changes with the batch, the cache length and
    how the new token was projected — and then converts the whole stack at
    the step's entry and exit; pinned, the step's only cache-sized ops are
    the in-place writes."""
    return with_layout_constraint(stack, Layout(tuple(range(stack.ndim))))


def at_layer(stack, layer, fn):
    """Run `fn` on layer `layer`'s slice of a stacked state tree (leading
    layer axis) and write the slice it returns back whole at `layer`:
    `fn(slice) -> (out, new_slice)`, returns (out, updated stack).  The
    layer scan carries the stack, so the write lands in place.  With no
    stack (no cache) it returns (fn(None)'s out, None)."""
    if stack is None:
        return fn(None)[0], None

    def read(a):
        return jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)

    def write(a, n):
        return jax.lax.dynamic_update_index_in_dim(a, n.astype(a.dtype),
                                                   layer, 0)

    out, new = fn(jax.tree.map(read, stack))
    return out, jax.tree.map(write, stack, new)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

_ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": lambda v: jnp.square(jax.nn.relu(v)),
}


def activation_fn(name: str):
    """The single source of the MLP/MoE activation table (silu / gelu /
    relu2).  Every function preserves the input dtype — callers apply it
    in whatever compute dtype the projections produced.  Raises ValueError
    on an unknown name rather than serving an un-activated hidden state."""
    try:
        return _ACTIVATIONS[name]
    except KeyError:
        raise ValueError(
            f"unknown activation {name!r}; expected one of "
            f"{sorted(_ACTIVATIONS)}") from None


def init_mlp(key: jax.Array, d: int, f: int, gated: bool,
             cim: Optional[CIMConfig] = None) -> Dict:
    """Up/down (+ optional gate) projection params for a d->f->d MLP."""
    ks = jax.random.split(key, 3)
    p = {"w_up": init_cim_linear(ks[0], d, f, cfg=cim),
         "w_down": init_cim_linear(ks[1], f, d, cfg=cim)}
    if gated:
        p["w_gate"] = init_cim_linear(ks[2], d, f, cfg=cim)
    return p


def mlp_block(params: Dict, x: jnp.ndarray, cim: CIMConfig,
              act: str = "silu",
              key: Optional[jax.Array] = None) -> jnp.ndarray:
    """(Gated) MLP with every projection through the CIM path.  `key`
    seeds the projections' noise model (distinct fold per projection)."""
    k_up = k_gate = k_down = None
    if key is not None:
        k_up, k_gate, k_down = (jax.random.fold_in(key, i)
                                for i in range(3))
    up = cim_linear_apply(params["w_up"], x, cim, key=k_up)
    up = shard(up, BATCH, None, TP)
    fn = activation_fn(act)
    if "w_gate" in params:
        gate = cim_linear_apply(params["w_gate"], x, cim, key=k_gate)
        gate = shard(gate, BATCH, None, TP)
        hidden = fn(gate) * up
    else:
        hidden = fn(up)
    y = cim_linear_apply(params["w_down"], hidden, cim, key=k_down)
    return shard(y, BATCH, None, None)
