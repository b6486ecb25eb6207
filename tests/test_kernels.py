"""Per-kernel sweep: Pallas cim_mbiw vs the pure-jnp oracle (the kernel runs
in the Pallas interpreter on CPU, compiled by Mosaic on a TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import digital_ref as dr
from repro.core.hw import DEFAULT_MACRO
from repro.kernels.cim_mbiw import ops
from repro.kernels.cim_mbiw.ref import cim_matmul_ref, cim_matmul_ref_serial


def _rand_case(m, k, n, r_in, r_w, seed):
    kx, kw, kg, kb = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.randint(kx, (m, k), 0, 2**r_in).astype(jnp.int32)
    w = dr.quantize_weight_odd(
        jax.random.randint(kw, (k, n), -(2**r_w - 1), 2**r_w), r_w)
    gamma = 2.0 ** jax.random.randint(kg, (n,), 0, 6).astype(jnp.float32)
    beta = jax.random.randint(kb, (n,), -16, 16).astype(jnp.float32)
    return x, w, gamma, beta


SHAPES = [
    (8, 36, 4, 1, 1, 1), (16, 144, 16, 4, 2, 4), (32, 256, 64, 8, 4, 8),
    (100, 1152, 64, 8, 4, 8), (17, 300, 33, 5, 3, 6), (64, 1000, 40, 8, 4, 4),
    (1, 128, 1, 8, 4, 8), (256, 512, 128, 7, 2, 8),
]


@pytest.mark.parametrize("m,k,n,r_in,r_w,r_out", SHAPES)
def test_kernel_matches_oracle(m, k, n, r_in, r_w, r_out):
    x, w, gamma, beta = _rand_case(m, k, n, r_in, r_w, seed=m + k + n)
    cfg = DEFAULT_MACRO
    units = cfg.units_for_rows(min(k, cfg.n_rows))
    g0 = dr.adc_gain_factor(r_in, r_w, r_out, units * cfg.rows_per_unit,
                            cfg.swing_efficiency(units), cfg.alpha_adc())
    got = ops.cim_matmul(x, w, gamma, beta, r_in=r_in, r_out=r_out, g0=g0)
    want = cim_matmul_ref(x, w, gamma, beta, g0=g0, r_out=r_out)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_block_shapes():
    """Different BlockSpec tilings give identical results."""
    x, w, gamma, beta = _rand_case(64, 512, 64, 8, 4, seed=0)
    g0 = dr.adc_gain_factor(8, 4, 8, 512)
    a = ops.cim_matmul(x, w, gamma, beta, r_in=8, r_out=8, g0=g0,
                       bm=128, bn=128, bk=128)
    b = ops.cim_matmul(x, w, gamma, beta, r_in=8, r_out=8, g0=g0,
                       bm=256, bn=256, bk=512)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_row_tiled_layer_matches_fakequant_layer():
    """kernels.ops.cim_linear (Pallas path) == core fakequant dp_hat path."""
    from repro.core import cim_layers as cl
    key = jax.random.PRNGKey(5)
    k_dim, n = 2000, 32
    x, w, gamma, beta = _rand_case(16, k_dim, n, 8, 4, seed=11)
    dp_hat = ops.cim_linear(x, w, gamma, beta, r_in=8, r_w=4, r_out=8)
    # reference: per-tile dequantized sum, same math as cim_layers
    cfg = DEFAULT_MACRO
    units = cfg.units_for_rows(min(k_dim, cfg.n_rows))
    g0 = dr.adc_gain_factor(8, 4, 8, units * cfg.rows_per_unit,
                            cfg.swing_efficiency(units), cfg.alpha_adc())
    want = jnp.zeros((16, n))
    for t in range((k_dim + 1151) // 1152):
        ks, ke = t * 1152, min((t + 1) * 1152, k_dim)
        codes = cim_matmul_ref(x[:, ks:ke], w[ks:ke], gamma, beta,
                               g0=g0, r_out=8)
        want = want + (codes.astype(jnp.float32) + 0.5 - 128 - beta) \
            / (gamma * g0)
    np.testing.assert_allclose(np.asarray(dp_hat), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("r_w", (1, 2, 4))
@pytest.mark.parametrize("r_in", (1, 2, 4, 8))
def test_precision_variant_matches_serial_oracle(r_in, r_w):
    """Dispatch variant == direct oracle == literal per-precision serial
    walk (bit-serial <=2b / nibble-serial >=3b input planes, 2^b weight
    column combination)."""
    r_out = 8
    x, w, gamma, beta = _rand_case(8, 72, 16, r_in, r_w, seed=r_in + 2 * r_w)
    cfg = DEFAULT_MACRO
    units = cfg.units_for_rows(72)
    g0 = dr.adc_gain_factor(r_in, r_w, r_out, units * cfg.rows_per_unit,
                            cfg.swing_efficiency(units), cfg.alpha_adc())
    fn = ops.kernel_variant(ops.KernelPrecision(r_in, r_w, r_out),
                            bm=128, bn=128, bk=128)
    got = fn(x, w, gamma, beta, g0)
    want = cim_matmul_ref(x, w, gamma, beta, g0=g0, r_out=r_out)
    serial = cim_matmul_ref_serial(x, w, gamma, beta, r_in=r_in, r_w=r_w,
                                   r_out=r_out, g0=g0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(serial), np.asarray(want))


def test_kernel_variant_cache_dedup():
    """Variants are shared across r_w (weights pre-decoded) and across
    r_in values with the same plane layout."""
    a = ops.kernel_variant(ops.KernelPrecision(8, 1, 8))
    b = ops.kernel_variant(ops.KernelPrecision(8, 4, 8))
    c = ops.kernel_variant(ops.KernelPrecision(5, 4, 8))   # also 2x4b planes
    d = ops.kernel_variant(ops.KernelPrecision(4, 4, 8))   # 1 plane
    e = ops.kernel_variant(ops.KernelPrecision(8, 4, 4))   # other epilogue
    assert a is b is c
    assert d is not a and e is not a


def test_split_planes():
    x = jnp.array([[0, 1, 15, 16, 255, 128]], jnp.int32)
    planes, n = ops.split_planes(x, 8)
    assert n == 2
    lo = np.asarray(planes[:, :6], np.int32)
    hi = np.asarray(planes[:, 6:], np.int32)
    np.testing.assert_array_equal(lo + 16 * hi, np.asarray(x))
    planes7, n7 = ops.split_planes(jnp.array([[127]], jnp.int32), 7)
    assert n7 == 1


@pytest.mark.parametrize("r_in,rows,k,n", [
    (8, 1024, 576, 256), (8, 16, 200, 64), (8, 802816, 9, 16),
    (2, 4, 1152, 256), (4, 16, 144, 107), (1, 1, 2000, 320)])
def test_fit_blocks_tpu_legal(r_in, rows, k, n):
    """Every fitted block obeys the TPU rule: each of the last two block
    dims is the whole (padded) array axis or a multiple of (8, 128).  With
    several input planes x is (M, P*K), so bk can never span its last axis
    and must be a multiple of 128 (the parent clamped it to 8)."""
    prec = ops.KernelPrecision(r_in, 4, 8)
    for pref in ((128, 128, 256), (32, 64, 1024), (256, 256, 512)):
        bm, bn, bk = ops.fit_blocks(prec.n_planes, rows, k, n, *pref)
        m_pad, n_pad = -(-rows // bm) * bm, -(-n // bn) * bn
        k_pad = -(-k // bk) * bk
        assert bm % 8 == 0
        assert bn % 128 == 0 or bn == n_pad
        if prec.n_planes > 1:
            assert bk % 128 == 0
        else:
            assert bk % 128 == 0 or bk == k_pad
        assert bm <= max(m_pad, 8) and k_pad - k < bk and n_pad - n < bn
