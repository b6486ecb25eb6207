"""Pallas TPU kernel for the CIM-MBIW quantized matmul with fused DSCI-ADC.

TPU adaptation of the macro's analog pipeline (DESIGN.md §3):
  * the DP array's charge accumulation    ->  int8 x int8 MXU matmul with an
    int32 VMEM accumulator (exact; the charge domain is linear, so is this);
  * the MBIW *input-serial* accumulation  ->  input planes walked by the K
    grid dimension, each plane's partial dp scaled by 2^(plane_shift*plane)
    into the same accumulator — the kernel literally performs the paper's
    input-serial, weight-parallel accumulation.  The plane granularity is
    the precision lever (paper Fig. 22): bit-serial (plane_shift=1) at
    r_in <= 2 where the macro runs its fastest/most-efficient modes,
    nibble-serial (plane_shift=4) at r_in >= 3 where the MXU makes 4b
    groups free and serialising to single bits would only waste it;
  * the DSCI-ADC with in-conversion ABN   ->  per-output-channel gamma/beta
    + floor + clip epilogue applied in VMEM before writeback, so the
    paper's "no post-ADC rescaling pass" maps to "no second pass over the
    output in HBM".

Grid: (M/bm, N/bn, P*K/bk) with the plane-major K axis innermost, so the
accumulator tile stays resident in VMEM across all planes and K blocks
(weight-stationary within a tile, like the macro).  The weight BlockSpec
re-reads the same w tile for every plane: w traffic is P-times redundant in
exchange for zero extra accumulator state — the right trade at P<=2.

VMEM at the default bm=bn=256, bk=512: x 128 KiB + w 128 KiB + acc 256 KiB
+ out 256 KiB < 1 MiB << 128 MiB VMEM; all dims MXU-aligned (128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import platform_call


def plane_layout(r_in: int) -> tuple[int, int]:
    """(plane_shift, n_planes) of the input-serial walk at a given r_in.

    Bit-serial below 3b (the macro's high-throughput binary modes),
    nibble-serial at 3-8b.  Weights stay *parallel* at every r_w — the
    MBIW combines weight bits spatially across adjacent columns, so the
    kernel sees them as pre-decoded odd integers.
    """
    if not 1 <= r_in <= 8:
        raise ValueError(f"r_in={r_in} outside the macro's 1-8b range")
    shift = 1 if r_in <= 2 else 4
    return shift, -(-r_in // shift)


def _cim_mbiw_kernel(x_ref, w_ref, gain_ref, beta_ref, o_ref, acc_ref, *,
                     n_k_total: int, n_k_inner: int, plane_shift: int,
                     r_out: int, fuse_adc: bool, interpret: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    plane = k // n_k_inner
    scale = (jnp.int32(1) << (plane_shift * plane)).astype(jnp.int32)
    part = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc_ref[...] += scale * part

    @pl.when(k == n_k_total - 1)
    def _epilogue():
        if not fuse_adc:
            # raw-dp mode: the caller owns the ADC conversion (the engine's
            # noise epilogue injects pre-floor terms it cannot fuse here)
            o_ref[...] = acc_ref[...]
            return
        dp = acc_ref[...].astype(jnp.float32)
        mid = 2.0 ** (r_out - 1)
        # float-op lockstep with ref.py: one rounded product, then
        # (mid + t) + beta.  The interpreter's XLA backend may FMA-contract
        # the product into the add in some fusion contexts (e.g. inside a
        # scan body) but not others, so there the product is pinned;
        # Mosaic keeps the two ops apart and has no lowering for the pin.
        t = gain_ref[...] * dp                         # (1, bn) * (bm, bn)
        if interpret:
            t = jax.lax.optimization_barrier(t)
        code = jnp.floor(mid + t + beta_ref[...])      # beta (1|bm, bn)
        o_ref[...] = jnp.clip(code, 0.0, 2.0 ** r_out - 1.0
                              ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "plane_shift", "g0", "r_out", "bm", "bn", "bk", "fuse_adc"))
def cim_mbiw_matmul_planes(x_planes: jnp.ndarray, w_q: jnp.ndarray,
                           gamma: jnp.ndarray, beta: jnp.ndarray, *,
                           plane_shift: int, g0: float, r_out: int,
                           bm: int = 256, bn: int = 256, bk: int = 512,
                           fuse_adc: bool = True) -> jnp.ndarray:
    """CIM matmul over input planes; shapes pre-padded to block multiples.

    x_planes : (M, P*K) int8 — P nibble planes laid out plane-major along
               the last axis; plane p carries bits [p*plane_shift, ...).
    w_q      : (K, N) int8 odd weights (+/-(2^r_w - 1))
    gamma    : (1, N) float32 ABN gain
    beta     : (1, N) float32 ABN offset in ADC codes — or (M, N) for a
               *per-GEMM-row* offset (segment-wise activation quantization
               folds a per-row zero-point into beta; the epilogue
               broadcasts either shape identically per element)
    returns  : (M, N) int32 ADC codes in [0, 2^r_out - 1], or the raw int32
               dp accumulator when `fuse_adc=False` (the noise-injected
               engine applies its own ADC epilogue after the kernel)

    The kernel is compiled by Mosaic in a program lowered for a TPU and
    interpreted in one lowered for the CPU (kernels/platform.py).
    """
    m, pk = x_planes.shape
    k_dim, n = w_q.shape
    assert pk % k_dim == 0, (pk, k_dim)
    n_planes = pk // k_dim
    assert m % bm == 0 and n % bn == 0 and k_dim % bk == 0, (m, n, k_dim)
    assert beta.shape in ((1, n), (m, n)), (beta.shape, m, n)
    n_k_inner = k_dim // bk
    n_k_total = n_planes * n_k_inner
    # the ADC gain, computed (and pinned) outside the kernel exactly as
    # ref.py computes it
    with jax.named_scope("cim.planes"):
        gain = jax.lax.optimization_barrier(gamma * g0)

    beta_spec = (pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
                 if beta.shape[0] == m and m != 1 else
                 pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))

    def build(interpret: bool):
        kernel = functools.partial(
            _cim_mbiw_kernel, n_k_total=n_k_total, n_k_inner=n_k_inner,
            plane_shift=plane_shift, r_out=r_out, fuse_adc=fuse_adc,
            interpret=interpret)
        return pl.pallas_call(
            kernel,
            grid=(m // bm, n // bn, n_k_total),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k % n_k_inner, j)),
                pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
                beta_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="cim_mbiw",
        )

    with jax.named_scope("cim.kernel"):
        return platform_call(build, x_planes, w_q, gain, beta)
