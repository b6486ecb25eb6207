"""Plain reference of one IMAGINE CIM layer, and its least work.

The layer (arXiv:2412.19750, Eqs. 1-7, in code space): activations are
quantized to r_in unsigned bits over the range of the call (one range per
segment of rows), weights to the odd integers of r_w bit-planes with one
scale per output channel, and each row tile of at most 1152 rows (K split
evenly) is converted by the ADC:

    code = clip(floor(2^(r_out-1) + gamma*g0*dp + beta_eff), 0, 2^r_out - 1)
    beta_eff = beta + gamma*g0 * (zero/scale) * colsum(w_q)

g0 is the code gain of the serial-split array at the tile's row count.
The partial codes are dequantized and summed digitally.  Written from the
paper's equations in plain jax.numpy; it shares no code with the program.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the macro (paper, Sec. III): 1152 rows in units of 36
N_ROWS, ROWS_PER_UNIT = 1152, 36
C_C, C_PAR_UNIT, C_LOAD = 0.7e-15, 2.0e-15, 40.0e-15
C_SAR, C_PAR_SAR = 33 * 0.7e-15, 2.0e-15
GAMMA_MIN = 2.0 ** -4

Point = Tuple[int, int, int]          # (r_in, r_w, r_out)


def row_tiles(k: int):
    """(start, size) of the even row tiles of a K-long reduction."""
    size = -(-k // -(-k // N_ROWS))
    return [(s, min(size, k - s)) for s in range(0, k, size)]


def code_gain(k: int, point: Point) -> float:
    """g0: ADC codes per unit of integer dot product at gamma = 1."""
    r_in, r_w, r_out = point
    units = -(-row_tiles(k)[0][1] // ROWS_PER_UNIT)
    n_dp = units * ROWS_PER_UNIT
    swing = n_dp * C_C / (n_dp * C_C + units * C_PAR_UNIT + C_LOAD)
    alpha_adc = C_SAR / (C_SAR + C_PAR_SAR)
    return swing / (2.0 * alpha_adc) * 2.0 ** (r_out - 1) / (
        n_dp * 2.0 ** (r_in + r_w))


def log_gamma_init(k: int, point: Point, max_gamma: float) -> float:
    """Distribution-aware ABN gain: the dot product's expected spread over
    one row tile fills a quarter of the ADC half-range."""
    r_in, r_w, r_out = point
    sigma = math.sqrt(row_tiles(k)[0][1]) * 2.0 ** r_in / 8.0 \
        * 2.0 ** (r_w - 1) / 2.0
    gamma = 0.25 * 2.0 ** (r_out - 1) / (code_gain(k, point) * sigma)
    return math.log2(min(max(gamma, 1.0), max_gamma))


def init_linear(key, k: int, n: int, point: Point,
                max_gamma: float) -> Dict[str, jnp.ndarray]:
    """Fan-in-scaled Gaussian weights, the analytic gain, zero offset."""
    return {"w": (1.0 / k) ** 0.5 * jax.random.normal(key, (k, n),
                                                      jnp.float32),
            "abn_log_gamma": jnp.full((n,), log_gamma_init(k, point,
                                                           max_gamma),
                                      jnp.float32),
            "abn_beta": jnp.zeros((n,), jnp.float32)}


def linear(x: jnp.ndarray, p: Dict[str, jnp.ndarray], point: Point,
           max_gamma: float, segments: Optional[jnp.ndarray] = None,
           n_segments: int = 1, dtype=jnp.float32) -> jnp.ndarray:
    """y ~= x @ w through the CIM layer; x (M, K), segments (M,) int32.

    Activation ranges are taken over all rows of a segment (over all rows
    when `segments` is None).  All activation arithmetic runs in `dtype`;
    the integer dot products are exact in any case."""
    r_in, r_w, r_out = point
    x = x.astype(dtype)
    levels = 2.0 ** r_in - 1.0
    if segments is None:
        zero, top = jnp.min(x), jnp.max(x)
    else:
        zero = jax.ops.segment_min(jnp.min(x, 1), segments,
                                   n_segments)[segments][:, None]
        top = jax.ops.segment_max(jnp.max(x, 1), segments,
                                  n_segments)[segments][:, None]
    scale = jnp.maximum(top - zero, 1e-8) * dtype(np.float32(1.0) /
                                                  np.float32(levels))
    q = jnp.round(jnp.clip((x - zero) / scale, 0.0, levels))
    full = 2.0 ** r_w - 1.0
    w = p["w"].astype(dtype)
    w_scale = jnp.maximum(jnp.max(jnp.abs(w), 0), 1e-8) * dtype(
        np.float32(1.0) / np.float32(full))
    u = jnp.clip(w / w_scale, -full, full)
    wq = jnp.clip(2.0 * jnp.round((u - 1.0) / 2.0) + 1.0, -full, full)
    gamma = jnp.clip(2.0 ** p["abn_log_gamma"].astype(dtype), GAMMA_MIN,
                     max_gamma)
    gain = gamma * dtype(code_gain(x.shape[1], point))
    beta = p["abn_beta"].astype(dtype)
    zp = zero / scale
    mid = 2.0 ** (r_out - 1)
    acc = jnp.zeros((x.shape[0], w.shape[1]), dtype)
    for ks, ksz in row_tiles(x.shape[1]):
        qt, wt = q[:, ks:ks + ksz], wq[ks:ks + ksz]
        dp = jnp.dot(qt.astype(jnp.bfloat16), wt.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(dtype)
        beta_eff = beta + gain * (zp * jnp.sum(wt, 0))
        code = jnp.clip(jnp.floor(mid + gain * dp + beta_eff), 0.0,
                        2.0 ** r_out - 1.0)
        acc = acc + (code + 0.5 - mid - beta) / gain
    return acc * scale * w_scale


def gemm_work(m: int, k: int, n: int, point: Point) -> Tuple[int, float]:
    """Least (ops, bytes) of one CIM GEMM from its shape and precision:
    2*M*K*N operations; weights at r_w bits, inputs at r_in bits and
    output codes at r_out bits, each moved once."""
    r_in, r_w, r_out = point
    return 2 * m * k * n, (k * n * r_w + m * k * r_in + m * n * r_out) / 8


def least_seconds(ops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """The larger of ops at the int8 peak and bytes at HBM bandwidth, and
    which of the two binds."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
