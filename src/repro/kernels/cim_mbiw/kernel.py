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


def _accumulate(x_ref, w_ref, acc_ref, k, n_k_inner: int,
                plane_shift: int):
    """acc += 2^(plane_shift*plane) * (x block . w block) for grid step k
    of the plane-major K walk."""
    plane = k // n_k_inner
    scale = (jnp.int32(1) << (plane_shift * plane)).astype(jnp.int32)
    part = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    acc_ref[...] += scale * part


def _write_codes(acc_ref, gain_ref, beta_ref, o_ref, *, r_out: int,
                 fuse_adc: bool, interpret: bool):
    """The ADC epilogue: the accumulated dp to codes, in VMEM."""
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
    t = gain_ref[...] * dp                             # (1, bn) * (bm, bn)
    if interpret:
        t = jax.lax.optimization_barrier(t)
    code = jnp.floor(mid + t + beta_ref[...])          # beta (1|bm, bn)
    o_ref[...] = jnp.clip(code, 0.0, 2.0 ** r_out - 1.0).astype(jnp.int32)


def _cim_mbiw_kernel(x_ref, w_ref, gain_ref, beta_ref, o_ref, acc_ref, *,
                     n_k_total: int, n_k_inner: int, plane_shift: int,
                     r_out: int, fuse_adc: bool, interpret: bool):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(x_ref, w_ref, acc_ref, k, n_k_inner, plane_shift)

    @pl.when(k == n_k_total - 1)
    def _epilogue():
        _write_codes(acc_ref, gain_ref, beta_ref, o_ref, r_out=r_out,
                     fuse_adc=fuse_adc, interpret=interpret)


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


def _cim_mbiw_grouped_kernel(grp_ref, src_ref, live_ref, x_ref, w_ref,
                             gain_ref, beta_ref, o_ref, acc_ref, *,
                             n_k_total: int, n_k_inner: int,
                             plane_shift: int, r_out: int, fuse_adc: bool,
                             interpret: bool):
    del grp_ref, src_ref          # consumed by the index maps
    i, k = pl.program_id(0), pl.program_id(2)
    live = live_ref[i] != 0

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _live():
        _accumulate(x_ref, w_ref, acc_ref, k, n_k_inner, plane_shift)

    last = k == n_k_total - 1

    @pl.when(last & live)
    def _epilogue():
        _write_codes(acc_ref, gain_ref, beta_ref, o_ref, r_out=r_out,
                     fuse_adc=fuse_adc, interpret=interpret)

    @pl.when(last & jnp.logical_not(live))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=(
    "plane_shift", "g0", "r_out", "bm", "bn", "bk", "fuse_adc"))
def cim_mbiw_grouped_planes(x_planes: jnp.ndarray, w_q: jnp.ndarray,
                            gamma: jnp.ndarray, beta: jnp.ndarray,
                            block_group: jnp.ndarray,
                            block_src: jnp.ndarray,
                            block_live: jnp.ndarray, *, plane_shift: int,
                            g0: float, r_out: int, bm: int, bn: int,
                            bk: int, fuse_adc: bool = True) -> jnp.ndarray:
    """The CIM matmul of `cim_mbiw_matmul_planes` over a leading group
    axis of weights: every block of `bm` GEMM rows belongs to one group
    and meets that group's weights, ABN gain and offset.

    x_planes    : (M, P*K) int8, M = B*bm, rows laid out group by group
    w_q         : (G, K, N) int8 odd weights, one (K, N) matrix a group
    gamma, beta : (B, 1, N) float32, the ABN gain and offset of each row
                  block (its group's, with its zero-point folded in)
    block_group : (B,) int32, the group whose weights a block meets
    block_src   : (B,) int32, the row block a block reads its inputs from
    block_live  : (B,) int32, 0 for a block that holds no live row

    The three block vectors reach the index maps by scalar prefetch, so
    the weight block follows the group.  An empty block computes nothing
    and writes zeros; give it its predecessor's group and source block and
    it moves no operand either.  Returns (M, N) int32 codes, or raw dp
    with `fuse_adc=False`.  The pallas_call is named `cim_mbiw` like the
    ungrouped one.
    """
    m, pk = x_planes.shape
    n_groups, k_dim, n = w_q.shape
    n_blocks = block_group.shape[0]
    assert pk % k_dim == 0, (pk, k_dim)
    n_planes = pk // k_dim
    assert m == n_blocks * bm and n % bn == 0 and k_dim % bk == 0, (
        m, n_blocks, bm, n, bn, k_dim, bk)
    assert gamma.shape == beta.shape == (n_blocks, 1, n), (gamma.shape,
                                                          beta.shape)
    n_k_inner = k_dim // bk
    n_k_total = n_planes * n_k_inner
    with jax.named_scope("cim.planes"):
        gain = jax.lax.optimization_barrier(gamma * g0)

    def build(interpret: bool):
        kernel = functools.partial(
            _cim_mbiw_grouped_kernel, n_k_total=n_k_total,
            n_k_inner=n_k_inner, plane_shift=plane_shift, r_out=r_out,
            fuse_adc=fuse_adc, interpret=interpret)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_blocks, n // bn, n_k_total),
            in_specs=[
                pl.BlockSpec((bm, bk),
                             lambda i, j, k, grp, src, live: (src[i], k)),
                pl.BlockSpec((pl.Squeezed(), bk, bn),
                             lambda i, j, k, grp, src, live:
                             (grp[i], k % n_k_inner, j)),
                pl.BlockSpec((pl.Squeezed(), 1, bn),
                             lambda i, j, k, *_: (i, 0, j)),
                pl.BlockSpec((pl.Squeezed(), 1, bn),
                             lambda i, j, k, *_: (i, 0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)])
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="cim_mbiw",
        )

    with jax.named_scope("cim.kernel"):
        return platform_call(build, block_group, block_src, block_live,
                             x_planes, w_q, gain, beta)
