"""jit'd public wrappers around the cim_mbiw Pallas kernel.

Handles everything the kernel does not: plane decomposition of unsigned
inputs (bit-serial at 1-2b, nibble-serial at 3-8b), padding to MXU-aligned
blocks, the macro's K<=1152 row-tiling with per-tile ADC conversion, and
dequantization back to real units (mirroring core/cim_layers).

Precision dispatch
------------------
`KernelPrecision` names one of the macro's operating points (r_in, r_w,
r_out); `kernel_variant` returns a jit-compiled kernel specialized to that
point (plane walk + accumulator shift from r_in, ADC epilogue from r_out)
and caches it, so a network executes through a small table of compiled
variants instead of re-tracing per layer.  `kernel_variant_for_tile`
additionally keys the cache on the dispatched call's geometry — block
sizes clamped to its (rows, k, n): one row tile's K by the local column
extent — so the smaller per-device extents of a sharded schedule do not
pad up to full-width blocks.  The runtime engine
(repro/runtime/engine.py) is the intended caller.

Units: inputs/weights are integer codes (unsigned < 2^r_in / odd ints in
+/-(2^r_w - 1)); outputs are int32 ADC codes in [0, 2^r_out) — or raw
int32 dp (integer dot-product units) with `fuse_adc=False`; gamma/beta are
the per-channel ABN gain (unitless) and offset (ADC code units); `g0` is
the unity-gain code gain in codes per dp unit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import digital_ref
from repro.core.hw import CIMMacroConfig, DEFAULT_MACRO
from repro.kernels.cim_mbiw.kernel import (cim_mbiw_grouped_planes,
                                          cim_mbiw_matmul_planes,
                                          plane_layout)

_PLANE_SHIFT = 4  # legacy nibble-plane default (r_in > 7 inputs)

SUPPORTED_R_IN = (1, 2, 3, 4, 5, 6, 7, 8)
SUPPORTED_R_W = (1, 2, 3, 4)
SUPPORTED_R_OUT = (1, 2, 3, 4, 5, 6, 7, 8)


@dataclasses.dataclass(frozen=True)
class KernelPrecision:
    """One (r_in, r_w, r_out) operating point of the macro."""
    r_in: int = 8
    r_w: int = 4
    r_out: int = 8

    def __post_init__(self):
        if self.r_in not in SUPPORTED_R_IN:
            raise ValueError(f"r_in={self.r_in} not in {SUPPORTED_R_IN}")
        if self.r_w not in SUPPORTED_R_W:
            raise ValueError(f"r_w={self.r_w} not in {SUPPORTED_R_W}")
        if self.r_out not in SUPPORTED_R_OUT:
            raise ValueError(f"r_out={self.r_out} not in {SUPPORTED_R_OUT}")

    @property
    def plane_shift(self) -> int:
        """Bits per input plane of the serial walk (1 bit-serial at
        r_in <= 2, 4 nibble-serial above)."""
        return plane_layout(self.r_in)[0]

    @property
    def n_planes(self) -> int:
        """Number of input planes the kernel walks (ceil(r_in/shift))."""
        return plane_layout(self.r_in)[1]


def _pad_to(x: jnp.ndarray, mult: Tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, (-s) % m) for s, m in zip(x.shape, mult)]
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def _pad_plane_k(x_planes: jnp.ndarray, n_planes: int, k_dim: int,
                 k_pad: int) -> jnp.ndarray:
    """Zero-pad every plane of a plane-major (M, P*K) operand to K + k_pad:
    zero inputs add nothing to the dp (the macro's trick for a layer that
    does not fill its 36-row units)."""
    m = x_planes.shape[0]
    xp = x_planes.reshape(m, n_planes, k_dim)
    xp = jnp.pad(xp, ((0, 0), (0, 0), (0, k_pad)))
    return xp.reshape(m, n_planes * (k_dim + k_pad))


def split_planes(x_q: jnp.ndarray, r_in: int,
                 plane_shift: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, int]:
    """Unsigned ints < 2^r_in -> plane-major int8 layout (M, P*K).

    With `plane_shift=None` (legacy): a single plane whenever the values fit
    in int8 (r_in <= 7), nibble planes above.  With an explicit shift the
    decomposition is ceil(r_in / shift) planes of `shift` bits each — the
    precision-specialized walk of `KernelPrecision`.
    """
    x = x_q.astype(jnp.int32)
    if plane_shift is None:
        if r_in <= 7:
            return x.astype(jnp.int8), 1
        plane_shift = _PLANE_SHIFT
    n_planes = -(-r_in // plane_shift)
    if n_planes == 1:
        return x.astype(jnp.int8), 1
    mask = 2**plane_shift - 1
    planes = [((x >> (plane_shift * p)) & mask).astype(jnp.int8)
              for p in range(n_planes)]
    return jnp.concatenate(planes, axis=-1), n_planes


def kernel_variant(prec: KernelPrecision, bm: int = 256, bn: int = 256,
                   bk: int = 512, fuse_adc: bool = True) -> Callable:
    """Precision-specialized kernel callable (cached per operating point).

    Returned fn: (x_q (M,K) uint<2^r_in, w_q (K,N) odd ints, gamma (N,),
    beta (N,), g0) -> (M,N) int32 ADC codes.  Shapes need not be padded.
    With `fuse_adc=False` the fn returns the raw int32 dp instead (gamma/
    beta/g0 ignored): the noise-injected engine epilogue owns the ADC.

    The cache is keyed on what the compiled kernel actually depends on —
    the (plane_shift, n_planes) input walk and the r_out epilogue — so
    operating points differing only in r_w (weights arrive pre-decoded)
    or sharing a plane layout (e.g. r_in 5-8) reuse one variant.
    """
    shift, n_planes = plane_layout(prec.r_in)
    return _kernel_variant(shift, n_planes, prec.r_out, bm, bn, bk,
                           fuse_adc)


_SUBLANE, _LANE = 8, 128


def _clamp_block(pref: int, dim: int, align: int = _SUBLANE) -> int:
    """Block along one axis of extent `dim`, preferring `pref`.

    The block is `pref` rounded up to `align`, or the whole padded axis
    (`dim` rounded up to 8) when that is no larger.  The TPU compiler
    takes a block whose last two dims are each the whole array axis or a
    multiple of (8, 128), so lane (last) axes pass align=128."""
    full = -(-dim // _SUBLANE) * _SUBLANE
    return min(full, -(-pref // align) * align)


def fit_blocks(n_planes: int, rows: int, k: int, n: int, bm: int, bn: int,
               bk: int) -> Tuple[int, int, int]:
    """(bm, bn, bk) for one dispatched (rows, k) x (k, n) tile, clamped to
    its geometry and legal for the TPU compiler.

    With several input planes the x operand is laid out (M, P*K), so a
    K block never spans its whole last axis and must be a multiple of 128:
    K pads up to it with zero rows, which add nothing to the dp.  Every
    choice is numerically identical (exact int32 accumulation +
    elementwise epilogue)."""
    if n_planes > 1:
        bk = -(-min(bk, k) // _LANE) * _LANE
    else:
        bk = _clamp_block(bk, k, _LANE)
    return _clamp_block(bm, rows), _clamp_block(bn, n, _LANE), bk


# preferred block-size palette the schedule autotuner (repro.tuner) searches;
# every entry is clamped per tile, so the palette over-covers small tiles
# harmlessly (duplicates collapse after clamping)
BM_PALETTE = (32, 64, 128, 256)
BN_PALETTE = (32, 64, 128, 256)
BK_PALETTE = (128, 256, 512, 1024)


def block_candidates(rows: int, k: int, n: int, n_planes: int = 1,
                     bms: Tuple[int, ...] = BM_PALETTE,
                     bns: Tuple[int, ...] = BN_PALETTE,
                     bks: Tuple[int, ...] = BK_PALETTE
                     ) -> Tuple[Tuple[int, int, int], ...]:
    """Deduplicated legal (bm, bn, bk) block choices for one dispatched
    tile of GEMM shape (rows, k) x (k, n).

    Each palette entry is fitted to the tile geometry and its `n_planes`
    input planes by `fit_blocks`, exactly as `kernel_variant_for_tile`
    fits its preferred blocks, so every returned choice names a real
    compiled variant — and because the kernel is numerically identical at
    any block size (exact int32 accumulation + elementwise epilogue),
    choosing among them can never change a bit.
    The schedule autotuner enumerates this set per layer."""
    out: list = []
    seen = set()
    for bm in bms:
        for bn in bns:
            for bk in bks:
                c = fit_blocks(n_planes, rows, k, n, bm, bn, bk)
                if c not in seen:
                    seen.add(c)
                    out.append(c)
    return tuple(out)


def kernel_variant_for_tile(prec: KernelPrecision, rows: int, k: int, n: int,
                            *, bm: int = 256, bn: int = 256, bk: int = 512,
                            fuse_adc: bool = True) -> Callable:
    """Kernel variant fitted to one dispatched call's geometry.

    Args:
      prec: the (r_in, r_w, r_out) operating point.
      rows, k, n: the call's GEMM shape — stream-chunk rows x row-tile K x
        the local column extent (every col tile the caller holds).  Under a
        sharded schedule these are the *per-device* extents, so each
        device compiles blocks sized to its own share instead of padding
        to full-width blocks.
      bm, bn, bk: preferred block sizes, fitted by `fit_blocks`.
    Returns:
      The cached callable of `kernel_variant` at the fitted block sizes —
      numerically identical at any block size (exact int32 accumulation +
      elementwise epilogue), so geometry fitting never changes a bit.
    """
    bm, bn, bk = fit_blocks(prec.n_planes, rows, k, n, bm, bn, bk)
    return kernel_variant(prec, bm=bm, bn=bn, bk=bk, fuse_adc=fuse_adc)


@functools.lru_cache(maxsize=None)
def _kernel_variant(shift: int, n_planes: int, r_out: int, bm: int, bn: int,
                    bk: int, fuse_adc: bool) -> Callable:
    r_eff = shift * n_planes          # widest r_in with this plane layout

    def run(x_q, w_q, gamma, beta, g0: float):
        return cim_matmul(x_q, w_q, gamma, beta, r_in=r_eff, r_out=r_out,
                          g0=g0, plane_shift=shift, bm=bm, bn=bn, bk=bk,
                          fuse_adc=fuse_adc)
    run.plane_shift = shift
    run.n_planes = n_planes
    run.r_out = r_out
    run.fuse_adc = fuse_adc
    return run


def cim_matmul(x_q: jnp.ndarray, w_q: jnp.ndarray, gamma: jnp.ndarray,
               beta: jnp.ndarray, *, r_in: int, r_out: int, g0: float,
               plane_shift: Optional[int] = None,
               bm: int = 256, bn: int = 256, bk: int = 512,
               fuse_adc: bool = True) -> jnp.ndarray:
    """One macro row-tile (K <= n_rows recommended): int inputs -> ADC codes.

    x_q: (M, K) unsigned ints < 2^r_in; w_q: (K, N) odd ints; gamma (N,);
    beta (N,) — or (M, N) for a per-GEMM-row offset (segment-wise
    activation quantization folds per-row zero-points into beta).
    Returns (M, N) int32 codes (raw int32 dp when `fuse_adc=False`).
    """
    m, k_dim = x_q.shape
    _, n = w_q.shape
    shift = _PLANE_SHIFT if plane_shift is None else plane_shift
    with jax.named_scope("cim.planes"):
        x_planes, n_planes = split_planes(x_q, r_in, plane_shift)
        # pad: K to bk multiple (per-plane), M to bm, N to bn
        k_pad = (-k_dim) % bk
        if k_pad:
            x_planes = _pad_plane_k(x_planes, n_planes, k_dim, k_pad)
            w_q = jnp.pad(w_q, ((0, k_pad), (0, 0)))
        x_planes = _pad_to(x_planes, (bm, 1))
        w_q = _pad_to(w_q.astype(jnp.int8), (1, bn))
        gamma2 = _pad_to(gamma.reshape(1, -1).astype(jnp.float32), (1, bn))
        if beta.ndim == 2 and beta.shape[0] == m and m != 1:
            # per-row offset: pad rows in lockstep with x (pad rows
            # discarded)
            beta2 = _pad_to(beta.astype(jnp.float32), (bm, bn))
        else:
            beta2 = _pad_to(beta.reshape(1, -1).astype(jnp.float32),
                            (1, bn))

    codes = cim_mbiw_matmul_planes(
        x_planes, w_q, gamma2, beta2, plane_shift=shift, g0=g0,
        r_out=r_out, bm=bm, bn=bn, bk=bk, fuse_adc=fuse_adc)
    with jax.named_scope("cim.recombine"):
        return codes[:m, :n]


def cim_matmul_grouped(x_q: jnp.ndarray, w_q: jnp.ndarray,
                       gamma: jnp.ndarray, beta: jnp.ndarray,
                       block_group: jnp.ndarray, block_src: jnp.ndarray,
                       block_live: jnp.ndarray, *, r_in: int, r_out: int,
                       g0: float, bm: int, bn: int = 512, bk: int = 1152,
                       fuse_adc: bool = True) -> jnp.ndarray:
    """One macro row-tile of a grouped call: int inputs -> ADC codes.

    x_q: (M, K) unsigned ints < 2^r_in, M = B*bm rows laid out in row
    blocks of one group each; w_q: (G, K, N) odd ints; gamma, beta: (B, N)
    the ABN gain and offset of each row block; block_group/src/live: (B,)
    int32 (see kernel.cim_mbiw_grouped_planes).  `bn`/`bk` are preferred
    blocks, fitted to (K, N) as `fit_blocks` fits them; `bm` is the row
    block of the layout and is used as given.  Returns (M, N) int32 codes
    (raw int32 dp when `fuse_adc=False`)."""
    m, k_dim = x_q.shape
    n = w_q.shape[2]
    shift, n_planes = plane_layout(r_in)
    _, bn, bk = fit_blocks(n_planes, bm, k_dim, n, bm, bn, bk)
    with jax.named_scope("cim.planes"):
        x_planes, n_planes = split_planes(x_q, r_in, shift)
        k_pad = (-k_dim) % bk
        if k_pad:
            x_planes = _pad_plane_k(x_planes, n_planes, k_dim, k_pad)
            w_q = jnp.pad(w_q, ((0, 0), (0, k_pad), (0, 0)))
        w_q = _pad_to(w_q.astype(jnp.int8), (1, 1, bn))
        gamma3 = _pad_to(gamma[:, None, :].astype(jnp.float32), (1, 1, bn))
        beta3 = _pad_to(beta[:, None, :].astype(jnp.float32), (1, 1, bn))
    codes = cim_mbiw_grouped_planes(
        x_planes, w_q, gamma3, beta3, block_group, block_src, block_live,
        plane_shift=shift, g0=g0, r_out=r_out, bm=bm, bn=bn, bk=bk,
        fuse_adc=fuse_adc)
    with jax.named_scope("cim.recombine"):
        return codes[:, :n]


def cim_linear(x_q: jnp.ndarray, w_q: jnp.ndarray, gamma: jnp.ndarray,
               beta: jnp.ndarray, *, r_in: int, r_w: int, r_out: int,
               cfg: CIMMacroConfig = DEFAULT_MACRO, adaptive_swing: bool = True
               ) -> jnp.ndarray:
    """Full layer: row-tiled kernel calls with per-tile ADC, digital
    partial-sum recombination in dp units (host side, like the chip).

    Returns (M, N) float32 dp_hat (caller applies act/weight scales)."""
    m, k_dim = x_q.shape
    n = w_q.shape[1]
    n_rows = cfg.n_rows
    if adaptive_swing:
        rows = min(k_dim, n_rows)
        units = cfg.units_for_rows(rows)
    else:
        units = cfg.n_units
    n_dp = units * cfg.rows_per_unit
    g0 = digital_ref.adc_gain_factor(r_in, r_w, r_out, n_dp,
                                     cfg.swing_efficiency(units),
                                     cfg.alpha_adc())
    mid = 2.0 ** (r_out - 1)
    row_tiles = -(-k_dim // n_rows)
    dp_hat = jnp.zeros((m, n), jnp.float32)
    for t in range(row_tiles):
        ks, ke = t * n_rows, min((t + 1) * n_rows, k_dim)
        codes = cim_matmul(x_q[:, ks:ke], w_q[ks:ke], gamma, beta,
                           r_in=r_in, r_out=r_out, g0=g0)
        dp_hat += (codes.astype(jnp.float32) + 0.5 - mid - beta[None, :]) \
            / (gamma[None, :] * g0)
    return dp_hat
