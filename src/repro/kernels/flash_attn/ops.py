"""Wrapper: (B,S,H,D)-layout entry, padding, full flash custom VJP, and the
shard_map context-parallel entry used under the production mesh.

Forward AND backward run as Pallas kernels (online-softmax fwd emitting the
row logsumexp; Dao-style bwd recomputing p from (q,k,lse)), so attention
never materializes an S^2 buffer in HBM in either direction.

Distribution (DESIGN.md §5): under a mesh the kernel runs inside shard_map
with q sequence-sharded over "model" (context parallelism — head counts of
the assigned archs are not uniformly divisible by 16) and k/v replicated
over "model" (one all-gather per layer).  Each shard passes its global
q-position offset into the kernel for causal/window masking; dk/dv
cotangents are psum'd automatically by shard_map's transpose of the
replicated k/v inputs.

All kernel calls are wrapped in jax.named_scope("vmem_kernel"): the dry-run
HLO analyzer uses the marker to account only the BlockSpec block streaming
as HBM traffic (launch/hlo_analysis.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P, get_abstract_mesh

from repro.kernels.flash_attn.kernel import (flash_attention_bhsd,
                                             flash_attention_bwd_bhsd)
from repro.kernels.flash_attn.ref import attention_ref
from repro.kernels.platform import platform_call

_FLOAT0 = jax.dtypes.float0


def _pad_axis(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _block_sizes(sq, sk, bq, bk):
    bq = min(bq, max(64, sq))
    bk = min(bk, max(64, sk))
    return bq, bk


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_core(q, k, v, q_off, causal: bool, window: int,
                bq: int, bk: int):
    out, _ = _fwd_impl(q, k, v, q_off, causal, window, bq, bk)
    return out


def _prep(q, k, v, bq, bk):
    qt = _pad_axis(jnp.swapaxes(q, 1, 2), 2, bq)       # (B,H,Sq',D)
    kt = _pad_axis(jnp.swapaxes(k, 1, 2), 2, bk)
    vt = _pad_axis(jnp.swapaxes(v, 1, 2), 2, bk)
    return qt, kt, vt


def _fwd_impl(q, k, v, q_off, causal, window, bq, bk):
    b, sq, h, d = q.shape
    sk, g = k.shape[1], k.shape[2]
    rep = h // g
    qt, kt, vt = _prep(q, k, v, bq, bk)
    with jax.named_scope("vmem_kernel"):
        out, lse = flash_attention_bhsd(
            qt, kt, vt, q_off, causal=causal, window=window, sk_valid=sk,
            rep=rep, bq=bq, bk=bk)
    return jnp.swapaxes(out[:, :, :sq], 1, 2), lse


def _fwd(q, k, v, q_off, causal, window, bq, bk):
    out, lse = _fwd_impl(q, k, v, q_off, causal, window, bq, bk)
    return out, (q, k, v, q_off, out, lse)


def _bwd(causal, window, bq, bk, res, g_out):
    q, k, v, q_off, out, lse = res
    b, sq, h, d = q.shape
    sk, g = k.shape[1], k.shape[2]
    rep = h // g
    qt, kt, vt = _prep(q, k, v, bq, bk)
    dot = _pad_axis(jnp.swapaxes(g_out, 1, 2), 2, bq)
    # delta_i = rowsum(dO * O)  (cheap, O(S*D))
    delta = jnp.sum(jnp.swapaxes(g_out, 1, 2).astype(jnp.float32)
                    * jnp.swapaxes(out, 1, 2).astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = _pad_axis(delta, 2, bq)
    with jax.named_scope("vmem_kernel"):
        dq, dk_h, dv_h = flash_attention_bwd_bhsd(
            qt, kt, vt, dot, lse, delta, q_off, causal=causal, window=window,
            sk_valid=sk, rep=rep, bq=bq, bk=bk)
    dq = jnp.swapaxes(dq[:, :, :sq], 1, 2).astype(q.dtype)
    # reduce per-q-head dk/dv over each kv group's rep heads
    dk_h = dk_h[:, :, :sk].reshape(b, g, rep, sk, d).sum(axis=2)
    dv_h = dv_h[:, :, :sk].reshape(b, g, rep, sk, d).sum(axis=2)
    dk = jnp.swapaxes(dk_h, 1, 2).astype(k.dtype)
    dv = jnp.swapaxes(dv_h, 1, 2).astype(v.dtype)
    d_off = np.zeros((1, 1), _FLOAT0)      # int input -> float0 cotangent
    return dq, dk, dv, d_off


_flash_core.defvjp(_fwd, _bwd)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    bq: int = 512, bk: int = 512,
                    q_offset: Optional[jnp.ndarray] = None):
    """q (B, Sq, H, D); k/v (B, Sk, G, D).  Returns (B, Sq, H, D)."""
    bq, bk = _block_sizes(q.shape[1], k.shape[1], bq, bk)
    if q_offset is None:
        q_offset = jnp.zeros((1, 1), jnp.int32)
    return _flash_core(q, k, v, q_offset, causal, window, bq, bk)


def flash_attention_sharded(q, k, v, causal: bool = True, window: int = 0,
                            bq: int = 512, bk: int = 512):
    """Context-parallel entry: q seq-sharded over "model", k/v replicated
    over "model", batch over ("pod","data").  Falls back to the plain call
    when the ambient mesh is empty or does not divide the shapes."""
    mesh = get_abstract_mesh()
    b, sq = q.shape[0], q.shape[1]
    if mesh.empty:
        return flash_attention(q, k, v, causal, window, bq, bk)
    names = set(mesh.axis_names)
    ba = tuple(a for a in ("pod", "data") if a in names)
    n_b = 1
    for a in ba:
        n_b *= mesh.shape[a]
    if b % max(n_b, 1):
        ba = ()
        n_b = 1
    tp = "model" if "model" in names else None
    n_tp = mesh.shape[tp] if tp else 1
    if tp is None or sq % n_tp or (sq // n_tp) < 128:
        tp = None
        n_tp = 1

    q_spec = P(ba if ba else None, tp, None, None)
    kv_spec = P(ba if ba else None, None, None, None)

    def body(q_l, k_l, v_l):
        if tp is not None:
            idx = jax.lax.axis_index(tp).astype(jnp.int32)
            off = (idx * (sq // n_tp)).reshape(1, 1)
        else:
            off = jnp.zeros((1, 1), jnp.int32)
        bq_l, bk_l = _block_sizes(q_l.shape[1], k_l.shape[1], bq, bk)
        return _flash_core(q_l, k_l, v_l, off, causal, window, bq_l, bk_l)

    return shard_map(body, mesh=mesh,
                     in_specs=(q_spec, kv_spec, kv_spec),
                     out_specs=q_spec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# ring-buffer decode attention (the CIMDecodeLM serving step)
# ---------------------------------------------------------------------------
#
# Decode-time attention over per-row ring-buffer KV state is a different
# shape class from the prefill kernel above: one query per row, each row
# attending only to its OWN (L, H, hd) ring, with ring-slot validity
# expressed as a precomputed additive bias (slots the row has not written
# yet sit out of positional order, so the index-generated causal/window
# masks of `flash_attention` cannot describe them).  The whole working set
# is small (R <= slot capacity, L = KV window), so the kernel holds it in
# one VMEM block — no online softmax, no KV grid.  Rows and heads are
# flattened into one leading batch axis (the batch layout Mosaic lowers),
# and the digital oracle computes the same batched dots on the same
# layout with plain einsums, which keeps the two bit-exact in the
# interpreter (tests/test_scheduler.py asserts equality, not closeness).
# Both dots ask for full f32 precision: on a TPU the default would round
# the operands to bf16, and the two compilers need not round alike.

_HI = jax.lax.Precision.HIGHEST


def _rows_heads_flat(q, k, v, bias):
    """(R, H, hd), (R, L, H, hd) x2, (R, L) -> one (row, head) batch axis:
    (R*H, 1, hd), (R*H, L, hd) x2, (R*H, 1, L)."""
    r, h, hd = q.shape
    n_l = k.shape[1]

    def kv(a):
        return jnp.swapaxes(a, 1, 2).reshape(r * h, n_l, hd)
    b = jnp.broadcast_to(bias[:, None, :], (r, h, n_l))
    return (q.reshape(r * h, 1, hd), kv(k), kv(v),
            b.reshape(r * h, 1, n_l))


@jax.jit
def ring_decode_attention_ref(q, k, v, bias) -> jnp.ndarray:
    """Pure-jnp digital oracle of ring-buffer decode attention.

    q (R, H, hd); k/v (R, L, H, hd) — each row's own KV ring; bias (R, L)
    additive scores mask (0 for valid ring slots, -1e9 for unwritten).
    Returns (R, H, hd).  Oracle and kernel are both jitted as one unit so
    the bit-exactness contract is equality, not closeness."""
    qf, kf, vf, bf = _rows_heads_flat(q, k, v, bias)
    scores = jnp.einsum("bqd,bld->bql", qf, kf,
                        precision=_HI) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(scores + bf, axis=-1)
    out = jnp.einsum("bql,bld->bqd", probs, vf, precision=_HI)
    return out.reshape(q.shape)


def _ring_decode_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, *,
                        scale: float):
    s = jax.lax.dot_general(q_ref[...], k_ref[...],
                            (((2,), (2,)), ((0,), (0,))), precision=_HI,
                            preferred_element_type=jnp.float32) / scale
    probs = jax.nn.softmax(s + b_ref[...], axis=-1)
    o_ref[...] = jax.lax.dot_general(
        probs, v_ref[...], (((2,), (1,)), ((0,), (0,))), precision=_HI,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@jax.jit
def ring_decode_attention(q, k, v, bias) -> jnp.ndarray:
    """Pallas ring-buffer decode attention (bit-exact with
    `ring_decode_attention_ref` in the interpreter).

    Same shapes as the ref: q (R, H, hd), k/v (R, L, H, hd), bias (R, L).
    One pallas_call over the whole (VMEM-resident) decode working set."""
    scale = float(np.sqrt(q.shape[-1]))
    qf = _rows_heads_flat(q, k, v, bias)[0]

    def build(interpret: bool):
        return pl.pallas_call(
            functools.partial(_ring_decode_kernel, scale=scale),
            out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
            interpret=interpret,
            name="ring_decode_attention",
        )

    with jax.named_scope("vmem_kernel"):
        out = platform_call(build, *_rows_heads_flat(q, k, v, bias))
    return out.reshape(q.shape)
