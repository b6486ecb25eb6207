"""Pallas TPU flash attention (beyond-paper optimization, EXPERIMENTS §Perf).

The dry-run roofline shows every training/prefill cell is memory-bound on
materialized S^2 score buffers.  This kernel keeps the whole
softmax(QK^T/sqrt(d))V inner loop VMEM-resident: HBM traffic collapses from
O(S^2 * H) to the BlockSpec-declared O(S * D * H) of q/k/v/out.

Grid: (B, H, Sq/bq, Sk/bk) with the KV axis innermost ("arbitrary"), online
softmax running in VMEM scratch (acc/m/l) across KV steps.  GQA is handled
by the k/v index_map (kv head = q head // rep).  Causal and sliding-window
masks are generated from program_ids — no mask operand traffic.

VMEM at bq=bk=512, D=128: q 128 KiB + k/v 256 KiB + scores 1 MiB (f32)
+ acc 256 KiB  << 128 MiB, MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import platform_call

NEG_INF = -1e30


def _mask(i, j, bq, bk, causal, window, sk_valid, q_off=0):
    q_pos = q_off + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    keep = k_pos < sk_valid
    if causal:
        keep &= q_pos >= k_pos
    if window > 0:
        keep &= (q_pos - k_pos) < window
    return keep


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                  m_ref, l_ref, *, n_kv: int, bq: int, bk: int, causal: bool,
                  window: int, sk_valid: int, scale: float):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block
    q_off = off_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    s = jnp.where(_mask(i, j, bq, bk, causal, window, sk_valid, q_off),
                  s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                             # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l_safe)).astype(lse_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sk_valid", "rep", "bq", "bk"))
def flash_attention_bhsd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         q_off: jnp.ndarray = None, *,
                         causal: bool, window: int = 0, sk_valid: int = 0,
                         rep: int = 1, bq: int = 512,
                         bk: int = 512) -> jnp.ndarray:
    """q (B, H, Sq, D); k/v (B, G, Sk, D) with H = G * rep; pre-padded to
    block multiples.  sk_valid masks KV padding (0 -> all valid).
    q_off: (1,1) int32 — global position of q row 0 (context parallelism:
    each sequence shard passes its own offset)."""
    if q_off is None:
        q_off = jnp.zeros((1, 1), jnp.int32)
    b, h, sq, d = q.shape
    _, g, sk, _ = k.shape
    assert h == g * rep, (h, g, rep)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    n_kv = sk // bk
    sk_valid = sk_valid or sk
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, bq=bq, bk=bk, causal=causal,
        window=window, sk_valid=sk_valid, scale=scale)
    grid = (b, h, sq // bq, n_kv)
    return platform_call(lambda interpret: pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b_, h_, i, j: (0, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j, rep=rep: (b_, h_ // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, i, j, rep=rep: (b_, h_ // rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    ), q_off, q, k, v)


# ---------------------------------------------------------------------------
# backward kernels (flash bwd, Dao 2022 alg. 2 adapted to TPU grids)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         dl_ref, dq_ref, acc_ref, *, n_kv: int, bq: int,
                         bk: int, causal: bool, window: int, sk_valid: int,
                         scale: float):
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_off = off_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)            # (bq, 1)
    delta = dl_ref[0, 0].astype(jnp.float32)           # (bq, 1)

    s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    keep = _mask(i, j, bq, bk, causal, window, sk_valid, q_off)
    p = jnp.where(keep, jnp.exp(s - lse), 0.0)         # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    acc_ref[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == n_kv - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          dl_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                          n_q: int, bq: int, bk: int, causal: bool,
                          window: int, sk_valid: int, scale: float):
    j = pl.program_id(2)          # kv block
    i = pl.program_id(3)          # q block (innermost)
    q_off = off_ref[0, 0]

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)
    delta = dl_ref[0, 0].astype(jnp.float32)

    s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    keep = _mask(i, j, bq, bk, causal, window, sk_valid, q_off)
    p = jnp.where(keep, jnp.exp(s - lse), 0.0)         # (bq, bk)
    dv_acc[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale                      # (bq, bk)
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "sk_valid", "rep", "bq", "bk"))
def flash_attention_bwd_bhsd(q, k, v, do, lse, delta, q_off=None, *,
                             causal: bool, window: int = 0, sk_valid: int = 0,
                             rep: int = 1, bq: int = 512, bk: int = 512):
    if q_off is None:
        q_off = jnp.zeros((1, 1), jnp.int32)
    """Backward: q/do (B,H,Sq,D), k/v (B,G,Sk,D), lse/delta (B,H,Sq,1).
    Returns (dq (B,H,Sq,D), dk/dv per q-head (B,H,Sk,D) — caller reduces
    over the rep q-heads of each kv group)."""
    b, h, sq, d = q.shape
    _, g, sk, _ = k.shape
    n_kv, n_q = sk // bk, sq // bq
    sk_valid = sk_valid or sk
    scale = 1.0 / (d ** 0.5)

    off_spec = pl.BlockSpec((1, 1), lambda b_, h_, i, j: (0, 0))
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda b_, h_, i, j, rep=rep: (b_, h_ // rep, j, 0))
    stat_spec = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0))

    dq = platform_call(lambda interpret: pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kv=n_kv, bq=bq, bk=bk,
                          causal=causal, window=window, sk_valid=sk_valid,
                          scale=scale),
        grid=(b, h, n_q, n_kv),
        in_specs=[off_spec, q_spec, kv_spec, kv_spec, q_spec, stat_spec,
                  stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    ), q_off, q, k, v, do, lse, delta)

    # dk/dv: grid transposed, q innermost; outputs per q-head
    q_spec2 = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, j, i: (b_, h_, i, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bk, d),
                            lambda b_, h_, j, i, rep=rep: (b_, h_ // rep, j, 0))
    kvh_spec2 = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0))
    stat_spec2 = pl.BlockSpec((1, 1, bq, 1),
                              lambda b_, h_, j, i: (b_, h_, i, 0))
    off_spec2 = pl.BlockSpec((1, 1), lambda b_, h_, j, i: (0, 0))
    dk, dv = platform_call(lambda interpret: pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q=n_q, bq=bq, bk=bk,
                          causal=causal, window=window, sk_valid=sk_valid,
                          scale=scale),
        grid=(b, h, n_kv, n_q),
        in_specs=[off_spec2, q_spec2, kv_spec2, kv_spec2, q_spec2, stat_spec2,
                  stat_spec2],
        out_specs=[kvh_spec2, kvh_spec2],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    ), q_off, q, k, v, do, lse, delta)
    return dq, dk, dv
