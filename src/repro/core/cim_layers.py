"""CIM-quantized layers: the paper's technique as a composable JAX module.

`cim_linear_apply` is the single entry point used by every model in the repo
(MLP/LeNet for the paper's own workloads, and all 10 assigned LM
architectures).  Three execution modes:

  * "bypass"    : plain (bf16/fp32) matmul — the non-CIM baseline.
  * "fakequant" : the CIM-aware training/serving path.  Exact digital-
                  equivalent integer math (bit-plane weights, unsigned
                  activations, ABN-scaled floor ADC) with STE gradients and
                  optional post-silicon noise injection.  This is the TPU-
                  native adaptation: per-channel ABN is fused into the matmul
                  epilogue, the adaptive swing is the dynamic activation
                  scale (see DESIGN.md §3).
  * "sim"       : voltage-domain behavioural macro (core/cim_macro.py),
                  tiled per core/mapping.py.  Small workloads only; used by
                  fidelity tests and paper-figure benchmarks.
  * "engine"    : the precision-scalable inference runtime
                  (runtime/engine.py): the layer is planned into row/col
                  macro tiles and executed through the precision-
                  specialized Pallas kernel variants — the deployed
                  inference path, bit-exact with its digital reference
                  under NO_NOISE.  With cfg.noise enabled (and a key) the
                  runtime injects the post-silicon noise model through a
                  post-kernel epilogue — the fast path for Monte-Carlo
                  noise studies.

Parameters per layer: {"w": (K, N) fp32 master weights,
                       "abn_log_gamma": (N,), "abn_beta": (N,)}.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import abn as abn_lib
from repro.core import digital_ref, mapping
from repro.core import noise_model as nm
from repro.core.cim_macro import cim_macro_forward
from repro.core.hw import CIMMacroConfig, DEFAULT_MACRO
from repro.core.noise_model import NO_NOISE, NoiseConfig
from repro.core.quantization import (ActQuant, _static_reciprocal,
                                     adc_quantize, quantize_act,
                                     quantize_weight, rounding_barrier)


@dataclasses.dataclass(frozen=True)
class CIMConfig:
    """Per-layer CIM execution configuration."""
    mode: str = "fakequant"          # bypass | fakequant | sim
    r_in: int = 8
    r_w: int = 4
    r_out: int = 8
    adaptive_swing: bool = True      # serial-split DPL swing adaptation
    gamma_bits: int = -1             # -1: continuous gamma; >=0: HW quant
    max_gamma: float = 32.0          # resistive-ladder limit; the TPU-native
                                     # digital epilogue can exceed it (beyond-
                                     # paper mode, see DESIGN.md §3)
    noise: NoiseConfig = NO_NOISE
    macro: CIMMacroConfig = DEFAULT_MACRO
    sharding: Optional[object] = None   # runtime.engine.ShardingConfig —
                                        # multi-macro dispatch in mode
                                        # "engine" (ignored by other modes)
    isolate_rows: bool = False          # mode "engine" only: each leading
                                        # batch row is its own activation-
                                        # quantization segment, so fused
                                        # rows are bit-identical to solo
                                        # rows (serving-side isolation;
                                        # noise draws stay positional —
                                        # use runtime/scheduler.py for
                                        # full per-request noise identity)

    def replace(self, **kw) -> "CIMConfig":
        """A copy of this config with the given fields replaced."""
        return dataclasses.replace(self, **kw)


BYPASS = CIMConfig(mode="bypass")


def analytic_log_gamma_init(k: int, cfg: CIMConfig,
                            target_frac: float = 0.25) -> float:
    """Distribution-aware gamma init (no calibration data needed): scale the
    expected DP std of one macro row-tile to `target_frac` of the ADC
    half-range.  Assumes amax-scaled ~N activations/weights, for which the
    integer codes have std ~2^r_in/8 and ~2^(r_w-1)/2."""
    k_tile = -(-k // (-(-k // cfg.macro.n_rows)))   # rows per even row tile
    g0 = _code_gain(cfg, k)
    sigma_dp = (k_tile ** 0.5) * (2.0 ** cfg.r_in / 8.0) * (2.0 ** (cfg.r_w - 1) / 2.0)
    gamma = target_frac * 2.0 ** (cfg.r_out - 1) / (g0 * sigma_dp)
    import math
    gamma = min(max(gamma, 1.0), float(cfg.max_gamma))
    return math.log2(gamma)


def init_cim_linear(key: jax.Array, k: int, n: int,
                    w_init_scale: Optional[float] = None,
                    cfg: Optional[CIMConfig] = None) -> Dict:
    """Init one CIM linear: fan-in-scaled weights plus the per-output-
    column ABN gain/offset (gamma seeded analytically when `cfg` is
    given, else unity)."""
    scale = w_init_scale if w_init_scale is not None else (1.0 / k) ** 0.5
    lg = 0.0 if cfg is None else analytic_log_gamma_init(k, cfg)
    return {
        "w": scale * jax.random.normal(key, (k, n), jnp.float32),
        "abn_log_gamma": jnp.full((n,), lg, jnp.float32),
        "abn_beta": jnp.zeros((n,), jnp.float32),
    }


def _code_gain(cfg: CIMConfig, k_dim: int) -> float:
    """Unity-gain codes-per-integer-dp (Eq. 7 collapsed, digital_ref).

    K > n_rows splits into the even row tiles of mapping.map_layer, so the
    swing (and hence g0) follows rows-per-tile — keeping this path in
    lockstep with the runtime engine's per-tile ADC configuration."""
    macro = cfg.macro
    if cfg.adaptive_swing:
        row_tiles = -(-k_dim // macro.n_rows)
        rows = -(-k_dim // row_tiles)
        units = macro.units_for_rows(rows)
    else:
        units = macro.n_units          # fixed full-array swing (baseline)
    n_dp = units * macro.rows_per_unit
    swing = macro.swing_efficiency(units)
    return digital_ref.adc_gain_factor(cfg.r_in, cfg.r_w, cfg.r_out, n_dp,
                                       swing, macro.alpha_adc())


def cim_linear_apply(params: Dict, x: jnp.ndarray, cfg: CIMConfig,
                     key: Optional[jax.Array] = None) -> jnp.ndarray:
    """y ~= x @ w, executed through the configured CIM path.

    x: (..., K).  Returns (..., N) dequantized activations.
    """
    if cfg.mode == "deploy":
        # serving path: weights stored as int8 CIM codes + per-channel
        # scale (quantize_params_for_serving); the dequant fuses into the
        # matmul on TPU, so weight HBM traffic is the int8 bytes.
        wq = params["w_q"].astype(x.dtype) * params["w_scale"].astype(x.dtype)
        return x @ wq
    w = params["w"]
    if cfg.mode == "bypass":
        return x @ w.astype(x.dtype)
    if cfg.mode == "fakequant":
        return _fakequant_forward(params, x, cfg, key)
    if cfg.mode == "sim":
        return _sim_forward(params, x, cfg, key)
    if cfg.mode == "engine":
        return _engine_forward(params, x, cfg, key)
    raise ValueError(f"unknown CIM mode {cfg.mode!r}")


def quantize_params_for_serving(params, r_w: int = 4):
    """Convert every CIM-linear leaf dict {w, abn_*} into the deployed form
    {w_q int8, w_scale f32(N,), abn_*}: the macro's odd-integer weight grid
    stored in its natural int8 container (4x less weight HBM than fp32
    masters, 2x less than bf16).  Embeddings/norms stay untouched."""
    from repro.core.quantization import quantize_weight

    def convert(node):
        if isinstance(node, dict) and "router" in node:
            # MoE expert banks: (L, E, D, F) / (L, E, F, D) raw arrays
            out = dict(node)
            for k in ("w_gate", "w_up", "w_down"):
                if k in out:
                    wq = quantize_weight(out.pop(k), r_w, axis=-2)
                    out[f"{k}_q"] = wq.q.astype(jnp.int8)
                    out[f"{k}_scale"] = jnp.squeeze(wq.scale, axis=-2)
            return out
        if isinstance(node, dict) and "w" in node and "abn_log_gamma" in node:
            # works on stacked (L, K, N) leaves too: per-(layer, channel)
            # scales over the reduction axis
            wq = quantize_weight(node["w"], r_w, axis=-2)
            out = {k: v for k, v in node.items() if k != "w"}
            out["w_q"] = wq.q.astype(jnp.int8)
            out["w_scale"] = jnp.squeeze(wq.scale, axis=-2)
            return out
        if isinstance(node, dict):
            return {k: convert(v) for k, v in node.items()}
        return node

    return convert(params)


def _fakequant_forward(params: Dict, x: jnp.ndarray, cfg: CIMConfig,
                       key: Optional[jax.Array]) -> jnp.ndarray:
    w = params["w"]
    k_dim, n = w.shape
    compute_dtype = x.dtype
    # entry barrier, mirrored by _engine_forward: both modes quantize the
    # identical input float and hand the identical output float back to
    # the (identically-fused) digital glue between projections
    x32 = rounding_barrier(x.astype(jnp.float32))

    aq: ActQuant = quantize_act(x32, cfg.r_in)
    wq = quantize_weight(w, cfg.r_w, axis=0)

    gamma = abn_lib.abn_gamma(
        abn_lib.ABNParams(params["abn_log_gamma"], params["abn_beta"]),
        gamma_bits=cfg.gamma_bits, max_gamma=cfg.max_gamma)
    g0 = _code_gain(cfg, k_dim)
    mid = 2.0 ** (cfg.r_out - 1)

    if cfg.noise.enabled and key is not None:
        key, k2 = jax.random.split(key)
        # residual SA offset in code units (static per layer call): sampled
        # per *physical* macro column and gathered per logical channel, so
        # channels beyond one col tile's budget reuse the same residues —
        # matching the engine noise path (and the chip, which has exactly
        # n_cols comparators however wide the layer is)
        res_v = nm.sample_column_residues(k2, n, cfg.r_w, cfg.noise,
                                          cfg.macro)
        lsb_v = cfg.macro.alpha_adc() * cfg.macro.vddh / 2.0 ** (cfg.r_out - 1)
        # volts -> codes: static-reciprocal + barrier keeps the offset on
        # the ADC-floor path pinned (mirrors the engine's _layer_noise)
        offset_codes = rounding_barrier(gamma * res_v
                                        * _static_reciprocal(lsb_v))
    else:
        offset_codes = 0.0

    # K > n_rows splits into row tiles, each with its own ADC conversion;
    # partial codes are dequantized and summed digitally by the host —
    # exactly the macro-tiling of core/mapping.py (even split_k_slices,
    # matching the runtime engine's schedule).
    row_tiles = -(-k_dim // cfg.macro.n_rows)
    # the materialized ADC gain: floor/dequant must see the identical
    # float in every fusion context (see quantization.rounding_barrier)
    gain = rounding_barrier(gamma * g0)
    zp = aq.zero / aq.scale
    dp_hat = jnp.zeros(x32.shape[:-1] + (n,), jnp.float32)
    for ks, ksz in mapping.split_k_slices(k_dim, row_tiles):
        ke = ks + ksz
        # integer dot product (DP array + MBIW stages); exact in fp32 for
        # one macro row-tile (|dp| <= 1152*255*15 < 2^24).
        dp = aq.q[..., ks:ke] @ wq.q[ks:ke, :]
        # zero-point: x = q*s + z -> the z*colsum term is per-channel and
        # constant: folded into the ABN offset *inside* the ADC floor
        # (beta_eff = beta + gamma*g0*zp_dp), exactly the chip's
        # signed-to-unsigned conversion + beta block — and exactly the
        # engine kernel's fold, which makes this path bit-exact with
        # mode="engine" under NO_NOISE.
        zp_dp = zp * jnp.sum(wq.q[ks:ke, :], axis=0)
        if cfg.noise.enabled and key is not None:
            key, k1 = jax.random.split(key)
            # thermal noise referred to dp units through the code gain
            # (single expression shared with the engine noise epilogue)
            dp = dp + nm.thermal_sigma_dp(cfg.noise, cfg.r_out, g0) \
                * jax.random.normal(k1, dp.shape)
        beta_eff = (params["abn_beta"] + offset_codes) \
            + rounding_barrier(gain * zp_dp)
        code = adc_quantize(dp, r_out=cfg.r_out, gain=gain,
                            beta_codes=beta_eff)
        dp_hat = dp_hat + (code - mid - params["abn_beta"]) / gain

    y = rounding_barrier(dp_hat * aq.scale * wq.scale.reshape(-1))
    return y.astype(compute_dtype)


def _engine_config(cfg: CIMConfig):
    """The runtime EngineConfig mirroring a layer-level CIMConfig (the
    one mapping every engine-mode entry point shares, so equal layer
    configs hit one program-cache entry)."""
    from repro.runtime import engine as rt
    return rt.EngineConfig(macro=cfg.macro, adaptive_swing=cfg.adaptive_swing,
                           gamma_bits=cfg.gamma_bits, max_gamma=cfg.max_gamma,
                           noise=cfg.noise, sharding=cfg.sharding)


def _engine_forward(params: Dict, x: jnp.ndarray, cfg: CIMConfig,
                    key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Route the layer through the precision-scalable inference runtime.

    Inference only (no STE gradients); the layer fetches its compiled
    program from the module-level cache of runtime/program.py (keyed on
    the batch-bucketed LayerSpec + EngineConfig — planning happens once
    per distinct (shape, CIMConfig), never per call) and dispatches the
    precision-specialized Pallas kernel variant through the program's
    bucket executable.  cfg.noise propagates into the engine's
    noise-injected mode (requires `key`)."""
    # imported lazily: runtime.engine depends on this module for init
    from repro.runtime.program import DEFAULT_BUCKETS, compile_program

    k_dim, n = params["w"].shape
    lead = x.shape[:-1]
    # entry/exit barriers delimit the projection from the digital glue
    # around it: the glue between two projections then forms the same
    # isolated subgraph in an engine-mode and a fakequant-mode model, so
    # XLA fuses (and rounds) it identically in both — the stack-level
    # half of the bit-exactness contract (see _fakequant_forward)
    with jax.named_scope("cim.act_quant"):
        x2 = rounding_barrier(x.reshape((-1, k_dim)))
        segments = None
        if cfg.isolate_rows and lead:
            # one segment per leading batch row: (B, S, K) -> B segments
            # of S rows each, so fused rows quantize exactly as served
            # alone
            segments = jnp.repeat(jnp.arange(lead[0], dtype=jnp.int32),
                                  x2.shape[0] // lead[0])
    bucket = DEFAULT_BUCKETS.bucket_for(x2.shape[0])
    spec = mapping.LayerSpec(m=bucket, k=k_dim, n=n, r_in=cfg.r_in,
                             r_w=cfg.r_w, r_out=cfg.r_out)
    prog = compile_program([spec], _engine_config(cfg))
    y = prog.serve([params], x2, key, segments=segments)
    with jax.named_scope("cim.epilogue"):
        y = rounding_barrier(y)
        return y.reshape(lead + (n,)).astype(x.dtype)


def group_block_rows(cfg: CIMConfig) -> int:
    """Rows of one block of a grouped call's row layout (see
    cim_grouped_apply): a group's rows start at a multiple of this."""
    return _engine_config(cfg).bm


def init_cim_grouped(key: jax.Array, groups: int, k: int, n: int,
                     cfg: Optional[CIMConfig] = None) -> Dict:
    """G CIM linears of one (K, N) shape stacked on a leading group axis,
    each initialised as init_cim_linear initialises one."""
    return jax.vmap(lambda kk: init_cim_linear(kk, k, n, cfg=cfg))(
        jax.random.split(key, groups))


def cim_grouped_apply(params: Dict, x: jnp.ndarray, row_group: jnp.ndarray,
                      cfg: CIMConfig, *, ranges=None,
                      key: Optional[jax.Array] = None, group_rows: int = 0,
                      reference: bool = False) -> jnp.ndarray:
    """y[r] ~= x[r] @ w[row_group[r]]: one CIM layer shape over G weight
    sets, one kernel call a row tile whatever G is.

    params: {"w" (G, K, N), "abn_log_gamma" (G, N), "abn_beta" (G, N)};
    x: (M, K) rows laid out in blocks of `group_block_rows(cfg)` rows of
    one group each, a group's rows from a block boundary; row_group: (M,)
    int32 group of each row, G for a padding row (its output is
    meaningless).  In engine mode a group's activation range is taken over
    its own live rows (runtime.engine.grouped_layer), or is `ranges`
    (engine.group_ranges) when the layout is served in chunks;
    `key`/`group_rows` feed its noise model, `reference` its pure-jnp
    oracle.  Bypass mode is the float matmul.  Other modes raise
    ValueError."""
    if cfg.mode == "bypass":
        from repro.runtime.engine import _block_vectors
        bm = group_block_rows(cfg)
        grp = _block_vectors(row_group, params["w"].shape[0], bm)[0]
        y = jnp.einsum("bmk,bkn->bmn", x.reshape(grp.shape[0], bm, -1),
                       params["w"][grp].astype(x.dtype))
        return y.reshape(x.shape[0], -1)
    if cfg.mode != "engine":
        raise ValueError(f"a grouped CIM call does not support mode "
                         f"{cfg.mode!r}; use engine or bypass")
    from repro.runtime import engine as rt
    g, k_dim, n = params["w"].shape
    spec = mapping.LayerSpec(m=x.shape[0], k=k_dim, n=n, r_in=cfg.r_in,
                             r_w=cfg.r_w, r_out=cfg.r_out)
    with jax.named_scope("cim.act_quant"):
        x2 = rounding_barrier(x)
    y = rt.grouped_layer(spec, _engine_config(cfg), params, x2, row_group,
                         ranges=ranges, reference=reference, key=key,
                         group_rows=group_rows)
    with jax.named_scope("cim.epilogue"):
        return rounding_barrier(y).astype(x.dtype)


def cim_grouped_dense_apply(params: Dict, x: jnp.ndarray, cfg: CIMConfig,
                            *, key: Optional[jax.Array] = None,
                            reference: bool = False) -> jnp.ndarray:
    """(G, R, K) -> (G, R, N): every group's R rows through its own
    weights in one grouped call (cim_grouped_apply), each group's
    activation range over its R rows."""
    g, r, k_dim = x.shape
    if cfg.mode == "bypass":
        return jnp.einsum("grk,gkn->grn", x, params["w"].astype(x.dtype))
    bm = group_block_rows(cfg)
    r_pad = -(-r // bm) * bm
    xp = jnp.pad(x, ((0, 0), (0, r_pad - r), (0, 0)))
    live = jnp.arange(r_pad, dtype=jnp.int32) < r
    row_group = jnp.where(live[None, :],
                          jnp.arange(g, dtype=jnp.int32)[:, None], g)
    y = cim_grouped_apply(params, xp.reshape(g * r_pad, k_dim),
                          row_group.reshape(-1), cfg, key=key,
                          group_rows=r, reference=reference)
    return y.reshape(g, r_pad, -1)[:, :r]


def _sim_forward(params: Dict, x: jnp.ndarray, cfg: CIMConfig,
                 key: Optional[jax.Array]) -> jnp.ndarray:
    """Voltage-domain path: tile per mapping.py and run the behavioural
    macro.  No gradients (inference/fidelity only)."""
    w = params["w"]
    k_dim, n = w.shape
    lead = x.shape[:-1]
    x2 = x.reshape((-1, k_dim)).astype(jnp.float32)

    aq = quantize_act(x2, cfg.r_in)
    wq = quantize_weight(w, cfg.r_w, axis=0)
    planes_full = digital_ref.encode_weight_planes(
        wq.q.astype(jnp.int32), cfg.r_w)                  # (r_w, K, N)

    gamma = abn_lib.abn_gamma(
        abn_lib.ABNParams(params["abn_log_gamma"], params["abn_beta"]),
        gamma_bits=cfg.gamma_bits, max_gamma=cfg.max_gamma)
    spec = mapping.LayerSpec(m=x2.shape[0], k=k_dim, n=n, r_in=cfg.r_in,
                             r_w=cfg.r_w, r_out=cfg.r_out)
    mp = mapping.map_layer(spec, cfg.macro)
    mid = 2.0 ** (cfg.r_out - 1)
    lsb_v = cfg.macro.alpha_adc() * cfg.macro.vddh / 2.0 ** (cfg.r_out - 1)
    beta_v = params["abn_beta"] * lsb_v / gamma           # code -> volts

    # static per-physical-column SA residues, sampled once per layer and
    # shared by every row tile (the comparators don't change between
    # tiles) — same column mapping as the fakequant and engine paths
    if cfg.noise.enabled and key is not None:
        key, ksa = jax.random.split(key)
        sa_offset_v = nm.sample_column_residues(ksa, n, cfg.r_w, cfg.noise,
                                                cfg.macro)
    else:
        sa_offset_v = jnp.zeros((n,))

    dp_hat = jnp.zeros((x2.shape[0], n), jnp.float32)
    for (ks, ksz) in mapping.split_k_slices(k_dim, mp.row_tiles):
        xs = aq.q[:, ks:ks + ksz]
        ps = planes_full[:, ks:ks + ksz, :]
        if key is not None:
            key, sub = jax.random.split(key)
        else:
            sub = None
        code = cim_macro_forward(
            xs, ps, r_in=cfg.r_in, r_out=cfg.r_out, gamma=gamma,
            beta_v=beta_v, cfg=cfg.macro, noise=cfg.noise, key=sub,
            sa_offset_v=sa_offset_v)
        units = cfg.macro.units_for_rows(ksz)
        n_dp = units * cfg.macro.rows_per_unit
        g0 = digital_ref.adc_gain_factor(
            cfg.r_in, cfg.r_w, cfg.r_out, n_dp,
            cfg.macro.swing_efficiency(units), cfg.macro.alpha_adc())
        dp_hat = dp_hat + (code.astype(jnp.float32) + 0.5 - mid
                           - params["abn_beta"]) / (gamma * g0)
    y = dp_hat * aq.scale * wq.scale.reshape(-1)
    y = y + aq.zero * jnp.sum(wq.q * wq.scale, axis=0)    # zero-point term
    return y.reshape(lead + (n,)).astype(x.dtype)


def cim_conv2d_apply(params: Dict, x: jnp.ndarray, cfg: CIMConfig,
                     stride: int = 1, padding=1,
                     key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Conv2D through the CIM stack (the accelerator's stage (ii)).

    x: (B, H, W, C_in); params["w"]: (kh*kw*C_in, C_out) flattened filters.
    `padding` accepts an int, "SAME"/"VALID", or explicit per-edge pairs
    (mapping.resolve_padding).  mode="engine" plans the conv natively (the
    runtime performs the im2col streaming itself); every other mode
    materializes the patch tensor and detours through cim_linear_apply.
    """
    # lazy: runtime.engine lazily imports this module for init
    from repro.runtime.engine import im2col_patches

    k_flat, c_out = params["w"].shape
    kh = kw = int(round((k_flat // x.shape[-1]) ** 0.5))
    assert kh * kw * x.shape[-1] == k_flat, (kh, kw, x.shape, k_flat)
    b, h, w, c_in = x.shape
    spec = mapping.conv_layer_spec(
        batch=b, h=h, w=w, c_in=c_in, c_out=c_out, kh=kh, kw=kw,
        stride=stride, padding=padding,
        r_in=cfg.r_in, r_w=cfg.r_w, r_out=cfg.r_out)
    if cfg.mode == "engine":
        return _engine_conv_forward(params, x, cfg, spec, key)
    patches = im2col_patches(x, spec.conv)                # (B, OH, OW, kh*kw*C)
    return cim_linear_apply(params, patches, cfg, key)


def _engine_conv_forward(params: Dict, x: jnp.ndarray, cfg: CIMConfig,
                         spec: mapping.LayerSpec,
                         key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Route a conv layer through the runtime's native conv front-end via
    the program cache: the conv spec is rebuilt at the batch bucket, the
    compiled program is a cache hit after the first call for a given
    (geometry, CIMConfig), and dispatch pads/slices the batch through the
    bucket executable (cfg.noise propagates into the engine's
    noise-injected mode)."""
    from repro.runtime.program import DEFAULT_BUCKETS, compile_program

    g = spec.conv
    bucket = DEFAULT_BUCKETS.bucket_for(x.shape[0])
    if bucket != g.batch:
        spec = mapping.conv_layer_spec(
            batch=bucket, h=g.h, w=g.w, c_in=g.c_in, c_out=g.c_out,
            kh=g.kh, kw=g.kw, stride=g.stride, padding=g.padding,
            r_in=spec.r_in, r_w=spec.r_w, r_out=spec.r_out)
    prog = compile_program([spec], _engine_config(cfg))
    segments = None
    if cfg.isolate_rows:
        # one segment per batch image (the engine repeats ids over the
        # conv's out_h*out_w GEMM rows itself)
        segments = jnp.arange(x.shape[0], dtype=jnp.int32)
    return prog.serve([params], x, key, segments=segments).astype(x.dtype)
