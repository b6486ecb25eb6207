"""train_step / serve_step builders shared by the trainer, the server and
the dry-run."""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as tf
from repro.models.sharding import BATCH, TP, shard
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim import compression as gc
from repro.optim.schedules import cosine_schedule

AUX_LOSS_WEIGHT = 0.01


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean next-token CE; logits (B, S, V) bf16-safe."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def loss_fn(cfg: ModelConfig, params, batch: Dict) -> Tuple[jnp.ndarray, Dict]:
    kwargs = {}
    if "prefix_embeds" in batch:
        kwargs["prefix_embeds"] = batch["prefix_embeds"]
    if "encoder_frames" in batch:
        kwargs["encoder_frames"] = batch["encoder_frames"]
    logits, _, aux = tf.forward(cfg, params, batch["tokens"], **kwargs)
    if cfg.family == "vlm" and "prefix_embeds" in batch:
        logits = logits[:, batch["prefix_embeds"].shape[1]:]
    ce = cross_entropy(logits, batch["labels"])
    loss = ce + AUX_LOSS_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig, *,
                    total_steps: int = 10000, warmup: int = 100,
                    compress_grads: bool = False):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {"params", "opt", "err"?}; donate-able."""

    def train_step(state, batch):
        params = state["params"]
        (loss, parts), grads = jax.value_and_grad(
            functools.partial(loss_fn, cfg), has_aux=True)(params, batch)
        if compress_grads:
            # error-feedback int8 gradient compression before the
            # (XLA-inserted) cross-replica reduction (DESIGN.md §5)
            grads, new_err = gc.compressed_grads(grads, state["err"])
        lr_scale = cosine_schedule(state["opt"]["step"], warmup, total_steps)
        new_params, new_opt, om = adamw_update(
            params, grads, state["opt"], opt_cfg, lr_scale)
        new_state = {"params": new_params, "opt": new_opt}
        if compress_grads:
            new_state["err"] = new_err
        metrics = {"loss": loss, **parts, **om}
        return new_state, metrics

    return train_step


def init_train_state(cfg: ModelConfig, key: jax.Array,
                     compress_grads: bool = False) -> Dict:
    params = tf.init_params(cfg, key)
    state = {"params": params, "opt": adamw_init(params)}
    if compress_grads:
        state["err"] = gc.init_error_buffer(params)
    return state


def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        kwargs = {k: batch[k] for k in ("prefix_embeds", "encoder_frames")
                  if k in batch}
        logits, _, _ = tf.forward(cfg, params, batch["tokens"], **kwargs)
        return logits[:, -1, :]
    return prefill_step


def make_serve_step(cfg: ModelConfig, *, greedy: bool = True):
    """One decode step: (params, cache, tokens (B,1)) -> (next, cache)."""

    def serve_step(params, cache, tokens):
        logits, new_cache, _ = tf.forward(cfg, params, tokens, cache=cache)
        with jax.named_scope("lm.head"):
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return nxt[:, None], new_cache

    return serve_step
