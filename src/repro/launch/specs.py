"""Sharding rules + ShapeDtypeStruct input specs for every (arch x shape).

`param_specs` maps the param pytree to PartitionSpecs by leaf path
(Megatron TP on "model"; DP replication elsewhere).  `input_specs` builds
allocation-free stand-ins for the dry-run (the shannon/kernels pattern).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import transformer as tf

# (path regex, spec builder given leaf ndim) — first match wins.
# Specs are written for the *stacked* (leading layer axis) layout.
#
# 2-D weight matrices are FULLY sharded: TP ("model") on the Megatron axis
# AND FSDP/ZeRO ("pod","data") on the other matrix axis — without the FSDP
# axis, mixtral-8x22b/internvl2-76b fp32 masters + Adam moments exceed HBM
# (the dry-run's memory_analysis catches this).  XLA auto-inserts the
# per-layer weight all-gathers this implies, exactly like FSDP.
_FSDP = ("pod", "data")

_RULES = [
    # embeddings / lm head
    (r"embed$", lambda nd: P("model", _FSDP)),
    (r"lm_head/w(_q)?$", lambda nd: P(_FSDP, "model")),
    (r"pos_dec$", lambda nd: P(None, None)),
    # attention projections (stacked: L leading)
    (r"(attn|xattn)/w[qkv]/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), _FSDP, "model")),
    (r"(attn|xattn)/wo/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), "model", _FSDP)),
    (r"(attn|xattn)/b[qkv]$", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"(attn|xattn)/w[qkv]/abn_", lambda nd: P(*([None] * (nd - 1)), "model")),
    # MLP
    (r"mlp/w_(up|gate)/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), _FSDP, "model")),
    (r"mlp/w_down/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), "model", _FSDP)),
    (r"mlp/w_(up|gate)/abn_", lambda nd: P(*([None] * (nd - 1)), "model")),
    # MoE experts: (L, E, D, F) / (L, E, F, D); router replicated
    (r"moe/w_(up|gate)(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), _FSDP, "model")),
    (r"moe/w_down(_q)?$", lambda nd: P(*([None] * (nd - 2)), "model", _FSDP)),
    (r"moe/w_\w+_scale$", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"moe/router$", lambda nd: P()),
    # Mamba-2
    (r"mixer/in_proj/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), _FSDP, "model")),
    (r"mixer/in_proj/abn_", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"mixer/out_proj/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), "model", _FSDP)),
    (r"mixer/conv_w$", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"mixer/conv_b$", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"mixer/gate_norm$", lambda nd: P(*([None] * (nd - 1)), "model")),
    # RG-LRU
    (r"rec/w_(gelu|rnn)/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), _FSDP, "model")),
    (r"rec/w_(gelu|rnn)/abn_", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"rec/w_(a|x)$", lambda nd: P(*([None] * (nd - 2)), _FSDP, "model")),
    (r"rec/b_(a|x)$", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"rec/(conv_w|conv_b|lam)$", lambda nd: P(*([None] * (nd - 1)), "model")),
    (r"rec/w_out/w(_q)?$",
     lambda nd: P(*([None] * (nd - 2)), "model", _FSDP)),
]


def _path_to_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
    return "/".join(parts)


def _spec_for(path: str, leaf) -> P:
    for pat, builder in _RULES:
        if re.search(pat, path):
            return builder(leaf.ndim)
    return P()   # replicated


def _validate(spec: P, shape, mesh) -> P:
    """Filter spec axes that are absent from the mesh; keep the largest
    prefix of each tuple that still divides the dim (odd vocabs, tiny
    dims, missing 'pod' axis on the single-pod mesh)."""
    elems = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, e in zip(shape, elems):
        if e is None:
            out.append(None)
            continue
        axes = (e,) if isinstance(e, str) else tuple(e)
        axes = tuple(a for a in axes if a in mesh.axis_names)
        kept, prod = [], 1
        for a in axes:
            if dim % (prod * mesh.shape[a]) == 0:
                kept.append(a)
                prod *= mesh.shape[a]
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    return P(*out)


def param_specs(params, mesh) -> Any:
    """Pytree of PartitionSpecs matching `params`."""
    def one(path, leaf):
        spec = _spec_for(_path_to_str(path), leaf)
        return _validate(spec, leaf.shape, mesh)
    return jax.tree_util.tree_map_with_path(one, params)


def tree_shardings(spec_tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStructs — never allocate)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Stand-ins for one step's inputs.

    train  : tokens/labels (B, S) (+ modality stubs)
    prefill: tokens (B, S)
    decode : tokens (B, 1) + cache
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    sds = jax.ShapeDtypeStruct

    if shape.kind == "train":
        if cfg.family == "audio":
            lt = min(cfg.max_target_len, s // 8)
            return {"encoder_frames": sds((b, s, cfg.d_model), bf16),
                    "tokens": sds((b, lt), i32),
                    "labels": sds((b, lt), i32)}
        if cfg.family == "vlm":
            st = s - cfg.vision_tokens
            return {"prefix_embeds": sds((b, cfg.vision_tokens, cfg.d_model),
                                         bf16),
                    "tokens": sds((b, st), i32),
                    "labels": sds((b, st), i32)}
        return {"tokens": sds((b, s), i32), "labels": sds((b, s), i32)}

    if shape.kind == "prefill":
        if cfg.family == "audio":
            lt = min(cfg.max_target_len, 448)
            return {"encoder_frames": sds((b, s, cfg.d_model), bf16),
                    "tokens": sds((b, lt), i32)}
        if cfg.family == "vlm":
            st = s - cfg.vision_tokens
            return {"prefix_embeds": sds((b, cfg.vision_tokens, cfg.d_model),
                                         bf16),
                    "tokens": sds((b, st), i32)}
        return {"tokens": sds((b, s), i32)}

    if shape.kind == "decode":
        cache = jax.eval_shape(
            lambda: tf.init_cache(cfg, b, max_len=s))
        return {"tokens": sds((b, 1), i32), "cache": cache}

    raise ValueError(shape.kind)


def batch_specs(inputs: Dict[str, Any], mesh) -> Dict[str, Any]:
    """PartitionSpecs for the input pytree."""
    ba = batch_axes(mesh)

    def spec_of(path, leaf):
        p = _path_to_str(path)
        nd = len(leaf.shape)
        if p.startswith("cache"):
            if re.search(r"/kv/[kv]$", p) and nd == 5:
                # ring cache (L, S, B, G, hd): seq-sharded over model
                # (DESIGN.md §5)
                sp = P(None, "model", ba, None, None)
            elif re.search(r"/xkv/[kv]$", p) and nd == 5:
                # cross K/V (L, B, S, G, hd)
                sp = P(None, ba, "model", None, None)
            elif re.search(r"/ssm$", p) and nd == 5:
                sp = P(None, ba, "model", None, None)
            elif re.search(r"/conv$", p) and nd == 4:
                sp = P(None, ba, None, "model")
            elif re.search(r"/h$", p) and nd == 3:
                sp = P(None, ba, "model")
            else:
                sp = P()
        elif nd >= 2:
            sp = P(ba, *([None] * (nd - 1)))
        else:
            sp = P()
        return _validate(sp, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec_of, inputs)
