"""Precision-scalable CIM inference runtime (single- and multi-macro).

The paper's headline lever is workload-adaptive 8-to-1b precision scaling
(0.15-8 POPS/W); this module exposes it end-to-end: a network described as
`mapping.LayerSpec`s is *planned* into the macro's row/col tile schedule
(core/mapping.py) and *executed* through precision-specialized, jit-compiled
Pallas kernel variants (kernels/cim_mbiw/ops.kernel_variant), with the
chip's digital partial-sum recombination between row tiles.  Col tiles
stay the macro's unit of work (macro_evals, noise draws, col sharding),
but they never interact numerically, so execution dispatches one kernel
call per row tile over all of a device's col tiles.

    specs = [LayerSpec(m=256, k=1152, n=64, r_in=4, r_w=2), ...]
    engine = CIMInferenceEngine(specs)           # plans + builds dispatch
    params = engine.init_params(jax.random.PRNGKey(0))
    y = engine(params, x)                        # jit-compiled schedule
    y_ref = engine.reference(params, x)          # pure-jnp digital oracle

Grouped calls: `grouped_layer` runs one layer shape over G weight sets
(the experts a chip holds, MLA's absorbed per-head products) as one
kernel call a row tile.  Rows are laid out in blocks of `group_block_rows`
rows of one group each; the kernel (`cim_mbiw`, the same Pallas name)
takes each block's group by scalar prefetch, so the weight block follows
the group and an empty block computes nothing.  Activation ranges are per
group over its live rows, so a group's rows get the bits of one ungrouped
call over them.

Plan-once/serve-many: the deployment API lives in runtime/program.py —
`compile_program(specs, cfg)` returns an immutable `CIMProgram` (a
NetworkPlan plus an executable cache keyed on batch bucket, noise mode and
device count), `program.bind(params)` pre-quantizes the weights into a
`BoundProgram`, and `.serve`/`.serve_batch` dispatch ragged request batches
through a power-of-two bucket ladder with zero re-planning and zero
re-tracing after warmup.  `CIMInferenceEngine` (below) is a thin
compatibility wrapper over that cache, and this module's `run_network` is
the legacy per-call entry (DeprecationWarning, still bit-exact).

Convolution front-end: a `LayerSpec` built by `mapping.conv_layer_spec`
carries its NHWC `ConvGeometry`; the engine then consumes image
activations directly — the K = kh*kw*C_in row groups of the paper's
Sec. III/IV conv mapping are formed on the fly by an im2col streaming
stage (`im2col_patches` + optional `EngineConfig.stream_rows` chunking of
the patch rows through the kernel), and the GEMM output is reshaped back
to (B, out_h, out_w, C_out) for the next layer.  Max-pool epilogues
(`pools`) and the conv -> dense flatten are planned per layer, so a whole
CNN (e.g. LeNet: conv1 -> pool -> conv2 -> pool -> fc1 -> fc2) runs
through one engine:

    specs, acts, pools = models.cnn.lenet_engine_specs(batch=128)
    engine = CIMInferenceEngine(specs, activations=acts, pools=pools)
    logits = engine(params, images)              # (B, 28, 28, 1) -> (B, 10)

Multi-macro sharding: the 1152x256 macro is a building block — the paper's
system-level 40 TOPS/W numbers assume it is replicated.  With
`EngineConfig(sharding=ShardingConfig(devices=D))` each layer's schedule
partitions across a 1-D `jax.sharding.Mesh` of D devices through
`jax.shard_map` (the per-device body is the same cached Pallas
variant): layers with at least D independent col tiles shard those
(`mapping.shard_layer` kind "col", disjoint output channels per device);
layers with fewer col tiles shard the GEMM-row dimension M = B*OH*OW via
the same stream_rows-style row chunking ("rows" kind, weights replicated).
Both partitions are bit-exact with the single-device schedule — columns
and GEMM rows never interact before the digital partial-sum recombination,
and the noise model's per-tile draws are device-count independent (below).

    cfg = EngineConfig(sharding=ShardingConfig(devices=8))
    engine = CIMInferenceEngine(specs, cfg)      # same API, D-macro dispatch

Numerics: under NO_NOISE the engine is bit-exact with `reference` at every
supported precision — both walk identical tile schedules and evaluate the
identical ADC floor expression; the kernel's int32 accumulator is exact for
one macro row-tile (|dp| <= 1152*255*15 < 2^24).  The activation zero-point
is folded into the per-channel ABN beta *inside* the ADC floor
(beta_eff = beta + gamma*g0*zp_dp), exactly what the chip's
signed-to-unsigned conversion + beta block does.

Per-layer precision is free: each layer's (r_in, r_w, r_out) selects its
kernel variant from a small cached table, so a mixed-precision network
compiles one kernel per distinct operating point, not per layer; the
variant's block sizes are clamped to the dispatched call's geometry
(ops.kernel_variant_for_tile), so a sharded schedule's smaller per-device
extents do not pad up to full-width blocks.

Noise-injected mode (post-silicon studies, paper Sec. III.E/V.A): with
`EngineConfig(noise=NoiseConfig(...))` the full equivalent noise model runs
through the same planned schedule — the kernel variants dispatch in raw-dp
mode (`fuse_adc=False`) and a vectorized post-kernel epilogue applies, in
code units and at the exact points the fakequant/sim paths inject them:
per-physical-column SA offsets + 7b calibration residue (static per macro,
shared across col tiles), thermal kT/C noise on the dp, DPL settling INL
and MBIW charge-injection as gain terms on g0, and leakage droop.  Runs
take a PRNG key (`engine(params, x, key)`); thermal draws are generated
per (layer, row tile, col tile) over the layer's *full* GEMM-row extent
and sliced per stream chunk / device shard, so a fixed key is fully
deterministic AND invariant to both the stream_rows chunking and the
device count — sharded noisy inference is bit-exact with the
single-device path.  `CIMInferenceEngine.monte_carlo(params, x, key,
n_trials)` stacks seeded trials for Monte-Carlo accuracy-vs-noise sweeps.
Under NO_NOISE the fused bit-exact path is unchanged.

Compilation: only `NoiseConfig.enabled`/`.calibrated` are static (they
switch the kernel's fuse_adc path and the calibration branch); the numeric
sigma/offset/gain terms enter the jitted schedule as *traced* scalars
(NoiseConfig is a JAX pytree), so a sweep across noise operating points
shares one compile: `engine(params, x, key, noise=point_i)`.

Device scopes: every op of the hot path is traced inside one
`jax.named_scope` of a flat taxonomy, so a profiler trace can charge each
device op to the program layer that issued it (its `op_name` metadata
carries the scope; an op belongs to the innermost taxonomy scope there):

  cim.bind        weight quantization, ABN gamma, col-tile padding
                  (bind_layer; inside the executable only when unbound)
  cim.act_quant   pad-row pinning, activation quantization, zero-point,
                  bucket padding, a projection's entry reshape
  cim.im2col      im2col patches, reshape to GEMM rows, id repeats
  cim.zp_fold     per-row-tile weight column sums, beta_eff
  cim.planes      plane split, K/row/col padding, kernel operand slices,
                  the ADC gain pin before the pallas_call
  cim.kernel      the pallas_call (HLO name `cim_mbiw`)
  cim.recombine   dequant/accumulate of codes, stream-chunk concat
  cim.epilogue    scale multiply, activation, max-pool, reshape back
  cim.noise       noise fields and the noisy ADC epilogue
  lm.embed, lm.norm, lm.attention (RoPE, scores, softmax, PV; MLA's
  absorbed products are cim.* inside it), lm.kv_write (the KV or latent
  cache update), lm.head (logits, argmax), lm.moe_route (router logits,
  softmax, top-k), lm.moe_dispatch (the held experts' row layout and its
  gather), lm.moe_combine (weighted scatter-add of the experts' rows),
  lm.layer (the rest of a decoder layer: residuals, SwiGLU glue)

The `lm.*` scopes live in models/common.py, models/mla.py, models/moe.py,
models/transformer.py and launch/steps.py.  A scope is compile-time
metadata: it changes no computation, no trace count and no compile, and
is always on.  Renaming
one renames a benchmark reading (bench/scopes.py sums scopes by these
names).  `CIMProgram`'s bucketed dispatch runs inside the host span
`repro.serve` (runtime/program.py).

Units cheat-sheet (see also core/noise_model.py):
  * `dp` / `dp_hat`            — integer dot-product units (codes of the
                                  ideal digital MAC, pre-ADC);
  * `*_codes`                  — ADC output codes in [0, 2^r_out);
  * `g0`                       — codes per dp unit at gamma=1 (unitless);
  * `*_v`                      — volts (only inside the noise model);
  * activations in/out         — real-valued (dequantized) float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import abn as abn_lib
from repro.core import digital_ref, mapping
from repro.core import noise_model as nm
from repro.core.hw import CIMMacroConfig, DEFAULT_MACRO
from repro.core.noise_model import NO_NOISE, NoiseConfig
from repro.core.quantization import _static_reciprocal, rounding_barrier
from repro.kernels.cim_mbiw import ops as kops

Params = List[Dict[str, jnp.ndarray]]

# incremented once per jit trace of the schedule (a trace == a compile);
# tests assert that a noise operating-point sweep does not grow it
TRACE_COUNT = {"n": 0}

# incremented once per plan_network() that actually plans (a compiled
# program is planned exactly once; repeated dispatches through the
# runtime.program cache must be cache hits) — the planning-side mirror of
# TRACE_COUNT, asserted by tests/test_program.py
PLAN_COUNT = {"n": 0}

# thermal kT/C draws are generated per fixed-size global GEMM-row block
# (keys fold the block index), then sliced to the live extent: the values a
# given (layer, row tile, col tile, GEMM row) sees are invariant to the
# total row extent, so batch-bucket padding, stream_rows chunking and
# device sharding all reuse identical draws (jax's threefry bits are NOT
# prefix-stable across draw shapes, so a single full-extent draw would
# change every value whenever padding changed the extent)
NOISE_ROW_BLOCK = 128

_DEPRECATION = {"warned": False}


def _warn_legacy_entry(name: str) -> None:
    """One non-spammy DeprecationWarning per process for the per-call API."""
    if _DEPRECATION["warned"]:
        return
    _DEPRECATION["warned"] = True
    import warnings
    warnings.warn(
        f"{name} re-enters the engine per call; compile once with "
        "repro.runtime.program.compile_program(...) (or "
        "CIMInferenceEngine.compile()) and serve through the returned "
        "CIMProgram/BoundProgram for the plan-once/serve-many path",
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    """Multi-macro (multi-device) partitioning of the planned schedule.

    Attributes:
      devices: mesh size D; 0 means "every device jax reports at plan
        time".  The run raises if fewer devices are visible at dispatch.
      axis: mesh axis name (purely cosmetic; shows up in shard_map specs).

    Per-layer kind selection (col tiles vs GEMM rows) is automatic — see
    `mapping.shard_layer`.  A `devices=1` config is a valid degenerate
    case that still routes dispatch through shard_map on a 1-device mesh.
    """
    devices: int = 0
    axis: str = "macro"

    def resolve_devices(self) -> int:
        """Concrete mesh size: `devices`, or every visible device."""
        return self.devices if self.devices > 0 else jax.device_count()


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Execution configuration shared by every layer of a schedule."""
    macro: CIMMacroConfig = DEFAULT_MACRO
    adaptive_swing: bool = True      # serial-split DPL swing adaptation
    gamma_bits: int = -1             # -1: continuous gamma; >=0: HW quant
    max_gamma: float = 32.0
    bm: int = 128                    # kernel block sizes (MXU-aligned),
    bn: int = 512                    # clamped per dispatched call geometry:
    bk: int = 1152                   # a row tile's whole K (<= one macro's
                                     # rows) is one K block per plane, so the
                                     # weight block stays put across planes
    stream_rows: int = 0             # im2col streaming: GEMM rows per kernel
                                     # dispatch (0 = single dispatch); bounds
                                     # the Pallas working set for large maps
    noise: NoiseConfig = NO_NOISE    # post-silicon equivalent noise model;
                                     # enabled -> runs require a PRNG key
    sharding: Optional[ShardingConfig] = None  # multi-macro dispatch; None
                                     # keeps the single-device path

    def replace(self, **kw) -> "EngineConfig":
        """A copy with the given fields replaced (dataclasses.replace)."""
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One layer's macro-tile schedule.

    `n_slices` are *uniform* col tiles (mapping.split_even_slices): every
    tile spans `tile_n` channels and the covered extent `n_pad` may exceed
    spec.n — execution pads the column arrays and discards the excess.
    Uniformity is what lets col tiles dispatch SPMD across devices and
    keeps noise draws device-count independent.  `shard` is the layer's
    device partition (None on single-device plans).  `blocks` is an
    optional per-layer (bm, bn, bk) kernel block-size override (the
    schedule autotuner's knob); None uses the EngineConfig defaults —
    either way the kernel is numerically identical at any block size, so
    `blocks` only moves DMA traffic, never bits."""
    spec: mapping.LayerSpec
    mp: mapping.MacroMapping
    precision: kops.KernelPrecision
    g0: float                            # unity-gain codes per dp unit
    k_slices: Tuple[Tuple[int, int], ...]  # (start, size) row tiles
    n_slices: Tuple[Tuple[int, int], ...]  # (start, size) uniform col tiles
    activation: str = "none"             # "none" | "relu"
    pool: int = 1                        # max-pool window/stride epilogue
    shard: Optional[mapping.LayerShard] = None
    blocks: Optional[Tuple[int, int, int]] = None  # tuned (bm, bn, bk)

    @property
    def macro_evals(self) -> int:
        """Macro invocations per M-row batch: row tiles x col tiles."""
        return len(self.k_slices) * len(self.n_slices)

    @property
    def tile_n(self) -> int:
        """Channels per (uniform) col tile."""
        return self.n_slices[0][1]

    @property
    def n_pad(self) -> int:
        """Column extent covered by the uniform col tiles (>= spec.n)."""
        return len(self.n_slices) * self.tile_n

    @property
    def out_shape(self) -> Tuple[int, ...]:
        """Per-sample feature shape this layer emits (after pooling)."""
        g = self.spec.conv
        if g is None:
            return (self.spec.n,)
        return (g.out_h // self.pool, g.out_w // self.pool, g.c_out)


@dataclasses.dataclass(frozen=True)
class NetworkPlan:
    """An immutable, hashable planned schedule (the jit static argument)."""
    layers: Tuple[LayerPlan, ...]
    cfg: EngineConfig

    @property
    def precisions(self) -> Tuple[kops.KernelPrecision, ...]:
        """Distinct kernel operating points, in first-use order (the
        compiled-variant table of the schedule)."""
        seen: List[kops.KernelPrecision] = []
        for lp in self.layers:
            if lp.precision not in seen:
                seen.append(lp.precision)
        return tuple(seen)

    @property
    def total_macro_evals(self) -> int:
        """Schedule-wide macro invocations per M-row batch of work."""
        return sum(lp.macro_evals for lp in self.layers)


def _layer_g0(spec: mapping.LayerSpec, mp: mapping.MacroMapping,
              cfg: EngineConfig) -> float:
    macro = cfg.macro
    units = mp.units_per_tile if cfg.adaptive_swing else macro.n_units
    n_dp = units * macro.rows_per_unit
    return digital_ref.adc_gain_factor(
        spec.r_in, spec.r_w, spec.r_out, n_dp,
        macro.swing_efficiency(units), macro.alpha_adc())


def plan_layer(spec: mapping.LayerSpec, cfg: EngineConfig = EngineConfig(),
               activation: str = "none", pool: int = 1, *,
               blocks: Optional[Tuple[int, int, int]] = None,
               shard_kind: Optional[str] = None) -> LayerPlan:
    """Plan one layer: macro mapping, uniform col tiles, device partition.

    Args:
      spec: the GEMM/conv layer.
      cfg: shared execution config; cfg.sharding (if set) adds the layer's
        LayerShard for cfg.sharding.resolve_devices() macros.
      activation: "none" | "relu" epilogue.
      pool: max-pool window/stride (conv layers only, 1 = none).
      blocks: optional per-layer (bm, bn, bk) kernel block override (the
        schedule autotuner's winner); None keeps cfg.bm/bn/bk.  Numerics-
        neutral at any value (exact int32 accumulation).
      shard_kind: optional explicit "col"/"rows" shard kind (requires
        cfg.sharding); None keeps mapping.shard_layer's heuristic.
    Returns:
      LayerPlan (hashable; part of the jit-static NetworkPlan).
    """
    if pool < 1:
        raise ValueError(f"pool must be >= 1, got {pool}")
    if blocks is not None:
        blocks = tuple(int(b) for b in blocks)
        if len(blocks) != 3 or min(blocks) < 1:
            raise ValueError(f"blocks must be 3 positive ints, got {blocks}")
    if shard_kind is not None and cfg.sharding is None:
        raise ValueError("shard_kind override requires cfg.sharding")
    if pool > 1 and spec.conv is None:
        raise ValueError("pooling epilogue requires a conv layer")
    if spec.conv is not None:
        g = spec.conv
        if spec.k != g.kh * g.kw * g.c_in or spec.n != g.c_out:
            raise ValueError(
                f"conv geometry {g} inconsistent with GEMM view "
                f"k={spec.k} n={spec.n}")
        if pool > 1 and (g.out_h < pool or g.out_w < pool):
            raise ValueError(f"pool {pool} larger than conv output "
                             f"{g.out_h}x{g.out_w}")
    mp = mapping.map_layer(spec, cfg.macro)
    prec = kops.KernelPrecision(spec.r_in, spec.r_w, spec.r_out)
    shard = None
    if cfg.sharding is not None:
        shard = mapping.shard_layer(spec, mp, cfg.sharding.resolve_devices(),
                                    kind=shard_kind)
    return LayerPlan(
        spec=spec, mp=mp, precision=prec, g0=_layer_g0(spec, mp, cfg),
        k_slices=tuple(mapping.split_k_slices(spec.k, mp.row_tiles)),
        n_slices=tuple(mapping.split_even_slices(spec.n, mp.col_tiles)),
        activation=activation, pool=pool, shard=shard, blocks=blocks)


def _check_chain(layers: Sequence[LayerPlan]) -> None:
    """Feed-forward shape check across the mixed conv/dense chain: a dense
    layer's K must equal the flattened feature count of its predecessor, a
    conv layer's (h, w, c_in) must equal the predecessor's spatial output."""
    prev: Optional[LayerPlan] = None
    for i, lp in enumerate(layers):
        g = lp.spec.conv
        if prev is not None:
            out = prev.out_shape
            if g is None:
                feed = 1
                for d in out:
                    feed *= d
                if feed != lp.spec.k:
                    raise ValueError(
                        f"layer chain mismatch: layer {i-1} emits {out} "
                        f"({feed} features) but layer {i} expects "
                        f"k={lp.spec.k}")
            else:
                if len(out) != 3:
                    raise ValueError(
                        f"layer chain mismatch: conv layer {i} needs NHWC "
                        f"input but layer {i-1} emits flat {out}")
                if out != g.spatial_in:
                    raise ValueError(
                        f"layer chain mismatch: layer {i-1} emits {out} "
                        f"but conv layer {i} expects {g.spatial_in}")
                if prev.spec.conv is not None \
                        and prev.spec.conv.batch != g.batch:
                    raise ValueError(
                        f"layer chain mismatch: conv batch "
                        f"{prev.spec.conv.batch} != {g.batch} at layer {i}")
        prev = lp


def plan_network(specs: Sequence[mapping.LayerSpec],
                 cfg: EngineConfig = EngineConfig(),
                 activations: Optional[Sequence[str]] = None,
                 pools: Optional[Sequence[int]] = None, *,
                 schedule: Optional[Sequence] = None) -> NetworkPlan:
    """Plan a feed-forward network of dense and conv-tagged LayerSpecs.

    `activations`: per-layer epilogue nonlinearity; defaults to relu between
    layers and none after the last (the CNN workloads of the paper).
    `pools`: per-layer max-pool window/stride (1 = none, conv layers only),
    applied after the activation — together with the automatic conv -> dense
    flatten this covers the paper's LeNet-class CNNs.
    `schedule`: optional per-layer schedule overrides from the autotuner —
    one `None` (heuristic) or `(blocks, shard_kind)` pair per layer, where
    `blocks` is a (bm, bn, bk) tuple or None and `shard_kind` an explicit
    "col"/"rows" or None.  Overrides never change numerics, only which
    compiled kernel variants and device partition execute the same math.
    """
    specs = list(specs)
    if activations is None:
        activations = ["relu"] * (len(specs) - 1) + ["none"]
    if len(activations) != len(specs):
        raise ValueError("one activation per layer required")
    if pools is None:
        pools = [1] * len(specs)
    if len(pools) != len(specs):
        raise ValueError("one pool factor per layer required")
    if schedule is None:
        schedule = [None] * len(specs)
    if len(schedule) != len(specs):
        raise ValueError("one schedule override (or None) per layer "
                         "required")
    layers = tuple(plan_layer(
        s, cfg, act, pool,
        blocks=None if sc is None else sc[0],
        shard_kind=None if sc is None else sc[1])
        for s, act, pool, sc in zip(specs, activations, pools, schedule))
    _check_chain(layers)
    PLAN_COUNT["n"] += 1
    return NetworkPlan(layers=layers, cfg=cfg)


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------

def im2col_patches(x: jnp.ndarray, g: mapping.ConvGeometry) -> jnp.ndarray:
    """(B, H, W, C_in) -> (B, out_h, out_w, kh*kw*C_in) patch tensor whose
    trailing axis matches the engine's (K, N) weight layout.

    The patches are a one-hot convolution; full precision makes it an
    exact copy of the activations on a TPU too, where the default would
    round them to bf16."""
    patches = jax.lax.conv_general_dilated_patches(
        x, (g.kh, g.kw), (g.stride, g.stride), padding=list(g.padding),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    b, oh, ow, kf = patches.shape
    # conv_general_dilated_patches returns channel-major (C*kh*kw) features;
    # weights are laid out (kh*kw*C) — reorder to match (cf. cim_layers).
    patches = patches.reshape(b, oh, ow, g.c_in, g.kh * g.kw)
    return jnp.swapaxes(patches, -1, -2).reshape(b, oh, ow, kf)


def _pad_dim(x: jnp.ndarray, axis: int, size: int,
             value: float = 0.0) -> jnp.ndarray:
    """Pad `axis` of `x` up to `size` with a constant (no-op if already)."""
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


def bind_layer(lp: LayerPlan, params: Dict[str, jnp.ndarray],
               cfg: EngineConfig) -> Dict[str, jnp.ndarray]:
    """Precompute one layer's weight-side operands (the `bind` stage).

    Everything here depends only on the parameters and the plan — not on the
    activations — so a compiled program computes it once
    (`CIMProgram.bind(params)`) and removes weight quantization + ABN gamma
    evaluation from the per-call path; the legacy per-call entry points run
    the same function inside their jitted graph.

    Args:
      lp: the planned layer.
      params: {"w" (K, N), "abn_log_gamma" (N,), "abn_beta" (N,)}.
      cfg: shared execution config (gamma quantization settings).
    Returns:
      dict of arrays, column-padded to the plan's uniform col-tile extent:
      "wqq" (K, n_pad) odd-integer weight codes, "w_scale" (N,) dequant
      scale, "gamma_p"/"beta_p" (n_pad,) padded ABN gain/offset (gamma pads
      with 1.0 — it divides in the dequant).
    """
    from repro.core.quantization import quantize_weight
    with jax.named_scope("cim.bind"):
        wq = quantize_weight(params["w"], lp.spec.r_w, axis=0)
        gamma = abn_lib.abn_gamma(
            abn_lib.ABNParams(params["abn_log_gamma"], params["abn_beta"]),
            gamma_bits=cfg.gamma_bits, max_gamma=cfg.max_gamma)
        n_pad = lp.n_pad
        return {
            "wqq": _pad_dim(wq.q, 1, n_pad),
            "w_scale": wq.scale.reshape(-1),
            "gamma_p": _pad_dim(gamma, 0, n_pad, value=1.0),
            "beta_p": _pad_dim(params["abn_beta"], 0, n_pad),
        }


def bind_network(plan: NetworkPlan, params: Params) -> Tuple[Dict, ...]:
    """bind_layer over a whole plan: one weight-side operand dict per layer
    (the payload of a BoundProgram).  Validates the per-layer param count."""
    if len(params) != len(plan.layers):
        raise ValueError(f"{len(params)} param dicts for "
                         f"{len(plan.layers)} planned layers")
    return tuple(bind_layer(lp, p, plan.cfg)
                 for lp, p in zip(plan.layers, params))


def _mask_pad_rows(x: jnp.ndarray, m_valid: jnp.ndarray) -> jnp.ndarray:
    """Overwrite batch rows at index >= m_valid with a copy of row 0.

    Batch-bucketed dispatch pads the leading batch axis up to a bucket
    size; this runs before every layer so the padded rows are always
    duplicates of a live row when the dynamic activation quantization
    computes its global min/max (duplicates never move a min/max), keeping
    the valid rows bit-exact with an unpadded run — even in noise mode,
    where the padded rows decorrelate from their source within a layer."""
    idx = jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0],) + (1,) * (x.ndim - 1), 0)
    return jnp.where(idx < m_valid, x, x[:1])


@dataclasses.dataclass
class _LayerNoise:
    """Per-layer noise context of one engine run (built at trace time).

    `offset_codes`/`droop_codes` are per padded output column (code units);
    a col shard slices them.  `gain_mult` collects the deterministic INL terms
    (DPL settling, MBIW charge injection) as a multiplier on the code gain.
    `thermal` holds the pre-drawn kT/C noise in dp units for every
    (row tile, col tile) over the layer's full GEMM-row extent — shape
    (k_tiles, n_tiles_padded, rows, tile_n) — so slicing rows (stream
    chunks, row shards) or col tiles (device shards) never changes a
    draw: noisy execution is chunking- and device-count-invariant."""
    offset_codes: jnp.ndarray        # (n_cols_padded,) code units
    droop_codes: jnp.ndarray         # (n_cols_padded,) code units
    gain_mult: jnp.ndarray           # scalar multiplier on gamma * g0
    thermal: jnp.ndarray             # (KT, NT_pad, rows, tile_n) dp units

    def rows(self, sl: slice) -> "_LayerNoise":
        """The context restricted to a GEMM-row slice."""
        return dataclasses.replace(self, thermal=self.thermal[:, :, sl, :])


def _layer_noise(lp: LayerPlan, cfg: EngineConfig, noise: NoiseConfig,
                 gamma_p: jnp.ndarray, key: jax.Array, m: int,
                 row_ids: Optional[jnp.ndarray] = None,
                 row_sub: Optional[jnp.ndarray] = None) -> _LayerNoise:
    """Noise terms of one layer in code/dp units, injected exactly where the
    fakequant (thermal, SA residue) and sim (settling, charge injection,
    leakage) paths put them.  `noise` carries *traced* scalars; only its
    enabled/calibrated flags are static.  `gamma_p` is the column-padded
    ABN gain; `m` the layer's full GEMM-row extent (thermal draws cover it
    once, device/chunk slices reuse them).

    `row_ids`/`row_sub` (optional, (m,) int32) switch the thermal draws
    from *positional* global-row-block keys to *identity* keys: each GEMM
    row's draw folds its caller-assigned id (and an intra-sample counter
    for the conv im2col expansion) instead of its position in the batch.
    An in-flight scheduler derives ids from (request uid, token step), so
    a request's draws are invariant to its slot, its batchmates, and the
    dispatch extent — the noise-mode half of per-request isolation."""
    macro, spec = cfg.macro, lp.spec
    units = lp.mp.units_per_tile if cfg.adaptive_swing else macro.n_units
    # memory note: the thermal field is O(row_tiles * n_pad * m) floats
    # (m rounded up to NOISE_ROW_BLOCK) — the same order as the layer's
    # aq.q/dp_hat buffers the engine already materializes (a small constant
    # factor, not a new asymptotic class), but it is NOT bounded by
    # stream_rows.
    # static per-physical-column SA offsets after 7b calibration, shared
    # across col tiles (the macro is reused sequentially)
    res_v = nm.sample_column_residues(jax.random.fold_in(key, 0), spec.n,
                                      spec.r_w, noise, macro)
    res_v = _pad_dim(res_v, 0, gamma_p.shape[0])
    lsb0_v = macro.alpha_adc() * macro.vddh / 2.0 ** (spec.r_out - 1)
    # volts -> codes conversions feed the ADC floor: pre-fold the LSB
    # divide into a trace-time reciprocal and pin the products, exactly
    # like the gain*dp product in the ADC epilogue (cimcheck NB001/NB002)
    inv_lsb0 = _static_reciprocal(lsb0_v)
    offset_codes = rounding_barrier(gamma_p * res_v * inv_lsb0)
    # leakage droop on V_acc, attenuated by the weight-parallel combination
    droop_v = nm.leakage_droop(spec.r_in, macro.t_dp_ns, noise) \
        * (1.0 - 2.0 ** (-spec.r_w))
    droop_codes = rounding_barrier(gamma_p * droop_v * inv_lsb0)
    settle = nm.settle_fraction(units, macro.t_dp_ns, noise)
    ci = nm.charge_injection_gain(spec.r_in, noise, macro)
    sigma_dp = nm.thermal_sigma_dp(noise, spec.r_out, lp.g0)
    # one independent field per (row tile, col tile) spanning all GEMM rows,
    # generated in fixed NOISE_ROW_BLOCK-row blocks whose keys fold the
    # *global* (row tile, col tile, row block) indices: any partition of
    # rows or tiles across chunks/devices sees identical values, and a
    # batch-bucketed run (rows padded past the live extent) only *extends*
    # the field — the live-row prefix never changes
    tkey = jax.random.fold_in(key, 1)
    tsz = lp.tile_n
    n_blocks = -(-max(m, 1) // NOISE_ROW_BLOCK)

    def tile_field(ki: int, ni: int) -> jnp.ndarray:
        kt = jax.random.fold_in(jax.random.fold_in(tkey, ki), ni)
        if row_ids is not None:
            # identity-keyed draws: fold each row's caller id + intra-
            # sample counter, so the value a row sees depends only on
            # what it *is*, never on where it sits in the batch
            sub = (row_sub if row_sub is not None
                   else jnp.zeros_like(row_ids))

            def draw(rid, sb):
                rk = jax.random.fold_in(jax.random.fold_in(kt, rid), sb)
                return jax.random.normal(rk, (tsz,))
            return jax.vmap(draw)(row_ids, sub)
        blocks = [jax.random.normal(jax.random.fold_in(kt, b),
                                    (NOISE_ROW_BLOCK, tsz))
                  for b in range(n_blocks)]
        field = blocks[0] if n_blocks == 1 else jnp.concatenate(blocks)
        return field[:m]

    thermal = jnp.stack([
        jnp.stack([sigma_dp * tile_field(ki, ni)
                   for ni in range(len(lp.n_slices))])
        for ki in range(len(lp.k_slices))])
    return _LayerNoise(
        offset_codes=offset_codes, droop_codes=droop_codes,
        gain_mult=jnp.asarray(settle * (1.0 + ci), jnp.float32),
        thermal=thermal)


def _noise_adc_code(lp: LayerPlan, dp: jnp.ndarray, gamma: jnp.ndarray,
                    beta_eff: jnp.ndarray, nctx: _LayerNoise,
                    thermal: jnp.ndarray) -> jnp.ndarray:
    """ADC conversion of one row tile's raw dp over the local columns with
    the noise terms applied pre-floor — the engine-side mirror of
    fakequant's adc_quantize(dp + thermal, gain, beta + offsets).
    `thermal` is the row tile's pre-drawn kT/C field laid out over the
    local columns (dp units, already row-aligned)."""
    dp = dp.astype(jnp.float32) + thermal
    mid = 2.0 ** (lp.spec.r_out - 1)
    code = jnp.floor(mid + rounding_barrier(gamma * lp.g0
                                            * nctx.gain_mult * dp)
                     + beta_eff
                     + nctx.offset_codes - nctx.droop_codes)
    return jnp.clip(code, 0.0, 2.0 ** lp.spec.r_out - 1.0).astype(jnp.int32)


def _tile_schedule(lp: LayerPlan, q_rows: jnp.ndarray, zp: jnp.ndarray,
                   wqq: jnp.ndarray, gamma: jnp.ndarray, beta: jnp.ndarray,
                   *, matmul,
                   nctx: Optional[_LayerNoise] = None) -> jnp.ndarray:
    """One block of GEMM rows through the layer's tile schedule.

    `wqq`/`gamma`/`beta` span a whole number of uniform col tiles (the
    caller's local column extent — all tiles on a single-device run, one
    device's tiles under col sharding).  Col tiles never interact: the ADC
    floor, ABN gain/offset, zero-point fold and dequant are per output
    column and g0 is per layer, so each row tile is one `matmul` dispatch
    over the whole local extent.  Only row tiles keep their own ADC
    conversion and are recombined digitally.  `matmul` returns int32 ADC
    codes — or raw int32 dp when a noise context is given, in which case
    the ADC conversion (with the noise terms and the row tile's pre-drawn
    thermal field) runs here.  Returns dp_hat (rows, local cols) in dp
    units."""
    mid = 2.0 ** (lp.spec.r_out - 1)
    g0 = lp.g0
    with jax.named_scope("cim.recombine"):
        # materialized ADC gain: the fakequant reference and this schedule
        # must dequantize with the identical float in every fusion context
        # (quantization.rounding_barrier)
        gain = rounding_barrier(gamma * g0)
        acc = jnp.zeros((q_rows.shape[0], wqq.shape[1]), jnp.float32)
    for ki, (ks, ksz) in enumerate(lp.k_slices):
        ke = ks + ksz
        with jax.named_scope("cim.zp_fold"):
            # zero-point: x = q*s + z -> z*colsum is per-channel constant,
            # folded into the ABN offset inside the ADC floor
            zp_dp = zp * jnp.sum(wqq[ks:ke], axis=0)
            beta_eff = beta + rounding_barrier(gain * zp_dp)
        with jax.named_scope("cim.planes"):
            # the kernel's own scopes (cim.kernel) are innermost
            out = matmul(q_rows[:, ks:ke], wqq[ks:ke], gamma, beta_eff, g0)
        if nctx is None:
            codes = out
        else:
            with jax.named_scope("cim.noise"):
                # (local col tiles, rows, tile_n) -> (rows, local cols):
                # every element keeps the draw of its (row tile, col tile)
                th = jnp.swapaxes(nctx.thermal[ki], 0, 1).reshape(
                    q_rows.shape[0], wqq.shape[1])
                codes = _noise_adc_code(lp, out, gamma, beta_eff, nctx, th)
        with jax.named_scope("cim.recombine"):
            # digital partial-sum recombination in dp units; dequantizing
            # against the *raw* beta keeps the zero-point contribution in
            # dp_hat, exactly like the fakequant training path
            acc = acc + (codes.astype(jnp.float32) + 0.5 - mid
                         - beta[None, :]) / gain[None, :]
    return acc


def _schedule_rows(lp: LayerPlan, cfg: EngineConfig, q_rows: jnp.ndarray,
                   zp: jnp.ndarray, wqq: jnp.ndarray, gamma: jnp.ndarray,
                   beta: jnp.ndarray, *, matmul,
                   nctx: Optional[_LayerNoise]) -> jnp.ndarray:
    """Stream `q_rows` through the tile schedule in cfg.stream_rows chunks
    (the im2col streaming stage).  Quantization stays global (or
    per-segment — `zp` is then per-row and chunks alongside the rows) and
    the noise context pre-draws per-tile thermal fields over all rows, so
    chunking is bit-invariant — with or without noise."""
    m = q_rows.shape[0]
    chunk = cfg.stream_rows if cfg.stream_rows > 0 else max(m, 1)
    parts = []
    for s in range(0, max(m, 1), chunk):
        sl = slice(s, min(s + chunk, m))
        with jax.named_scope("cim.noise"):
            rows_nctx = nctx.rows(sl) if nctx is not None else None
        parts.append(_tile_schedule(
            lp, q_rows[sl], zp if zp.ndim == 0 else zp[sl], wqq, gamma,
            beta, matmul=matmul, nctx=rows_nctx))
    if len(parts) == 1:
        return parts[0]
    with jax.named_scope("cim.recombine"):
        return jnp.concatenate(parts, 0)


def _engine_mesh(sharding: ShardingConfig, devices: int):
    from repro.launch.mesh import make_engine_mesh
    return make_engine_mesh(devices, sharding.axis)


def _sharded_schedule(lp: LayerPlan, cfg: EngineConfig, q_rows: jnp.ndarray,
                      zp: jnp.ndarray, wqq: jnp.ndarray, gamma: jnp.ndarray,
                      beta: jnp.ndarray, *, matmul,
                      nctx: Optional[_LayerNoise]) -> jnp.ndarray:
    """Dispatch one layer's tile schedule across the device mesh.

    kind "col": the uniform col tiles (padded up to a multiple of the
    device count with all-zero dummy tiles) spread over the mesh axis —
    each device runs `_schedule_rows` on its contiguous tile group, output
    columns concatenate across devices.  kind "rows": the GEMM rows
    (zero-padded to a multiple of the device count) spread instead, every
    device holding the full weight tiles.  The per-device body is the same
    `_schedule_rows` the serial path runs, and all noise terms are
    pre-drawn outside the shard_map, so both kinds are bit-exact with the
    single-device schedule (padding only ever adds discarded rows/cols)."""
    from jax.sharding import PartitionSpec as P

    shard, m = lp.shard, q_rows.shape[0]
    mesh = _engine_mesh(cfg.sharding, shard.devices)
    ax = cfg.sharding.axis
    noisy = nctx is not None

    def body(q_l, zp_l, wq_l, g_l, b_l, *noise_l):
        nl = _LayerNoise(*noise_l) if noisy else None
        return _schedule_rows(lp, cfg, q_l, zp_l, wq_l, g_l, b_l,
                              matmul=matmul, nctx=nl)

    if shard.kind == "col":
        t_tot = shard.devices * shard.tiles_per_device
        n_tot = t_tot * lp.tile_n
        wqq = _pad_dim(wqq, 1, n_tot)
        gamma = _pad_dim(gamma, 0, n_tot, value=1.0)   # 1.0: dequant div
        beta = _pad_dim(beta, 0, n_tot)
        args = [q_rows, zp, wqq, gamma, beta]
        specs = [P(), P(), P(None, ax), P(ax), P(ax)]
        if noisy:
            args += [_pad_dim(nctx.offset_codes, 0, n_tot),
                     _pad_dim(nctx.droop_codes, 0, n_tot),
                     nctx.gain_mult, _pad_dim(nctx.thermal, 1, t_tot)]
            specs += [P(ax), P(ax), P(), P(None, ax, None, None)]

        out = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                            out_specs=P(None, ax), check_vma=False)(*args)
        return out                       # (m, n_tot); caller slices cols

    # kind == "rows": data-parallel over the GEMM-row dimension; a per-row
    # zero-point (segment quantization) shards with its rows, a global
    # scalar replicates
    m_tot = shard.devices * -(-max(m, 1) // shard.devices)
    q_pad = _pad_dim(q_rows, 0, m_tot)
    zp_arg = zp if zp.ndim == 0 else _pad_dim(zp, 0, m_tot)
    zp_spec = P() if zp.ndim == 0 else P(ax, None)
    args = [q_pad, zp_arg, wqq, gamma, beta]
    specs = [P(ax, None), zp_spec, P(), P(), P()]
    if noisy:
        args += [nctx.offset_codes, nctx.droop_codes, nctx.gain_mult,
                 _pad_dim(nctx.thermal, 2, m_tot)]
        specs += [P(), P(), P(), P(None, None, ax, None)]

    out = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                        out_specs=P(ax, None), check_vma=False)(*args)
    return out[:m]                       # drop row padding


def _layer_tiles(lp: LayerPlan, bind: Dict[str, jnp.ndarray],
                 x2: jnp.ndarray, cfg: EngineConfig, *, matmul,
                 key: Optional[jax.Array] = None,
                 noise: Optional[NoiseConfig] = None,
                 sharded: bool = False,
                 seg_rows: Optional[jnp.ndarray] = None,
                 nid_rows: Optional[jnp.ndarray] = None,
                 sub_rows: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Run one layer's tile schedule over (M, K) GEMM rows.

    `bind` carries the precomputed weight-side operands (bind_layer);
    activation quantization and the noise context (offsets, per-tile
    thermal fields) are built globally per call, then the schedule executes
    serially in stream chunks or sharded across the mesh — numerically
    identical paths.

    `seg_rows` (optional, (M,) int32) switches the activation quantization
    to per-segment statistics (quantize_act segment path): the zero-point
    becomes per-row and folds into a per-row beta_eff inside the ADC
    floor, so rows of different segments never share swing state.
    `nid_rows`/`sub_rows` key the noise model's thermal draws by row
    identity instead of position (see _layer_noise)."""
    from repro.core.quantization import quantize_act
    with jax.named_scope("cim.act_quant"):
        if seg_rows is None:
            aq = quantize_act(x2, lp.spec.r_in)
        else:
            aq = quantize_act(x2, lp.spec.r_in, segment_ids=seg_rows,
                              num_segments=x2.shape[0])
        zp = jnp.asarray(aq.zero / aq.scale, jnp.float32)
    n = lp.spec.n
    wqq, gamma_p, beta_p = bind["wqq"], bind["gamma_p"], bind["beta_p"]
    m = x2.shape[0]
    with jax.named_scope("cim.noise"):
        nctx = (_layer_noise(lp, cfg, noise, gamma_p, key, m,
                             row_ids=nid_rows, row_sub=sub_rows)
                if noise is not None else None)
    if sharded and lp.shard is not None:
        dp_hat = _sharded_schedule(lp, cfg, aq.q, zp, wqq, gamma_p, beta_p,
                                   matmul=matmul, nctx=nctx)
    else:
        dp_hat = _schedule_rows(lp, cfg, aq.q, zp, wqq, gamma_p, beta_p,
                                matmul=matmul, nctx=nctx)
    with jax.named_scope("cim.epilogue"):
        y = dp_hat[:, :n] * aq.scale * bind["w_scale"]
        if lp.activation == "relu":
            y = jax.nn.relu(y)
        elif lp.activation != "none":
            raise ValueError(f"unknown activation {lp.activation!r}")
        return y


def _run_layer(lp: LayerPlan, bind: Dict[str, jnp.ndarray], x: jnp.ndarray,
               cfg: EngineConfig, *, matmul,
               key: Optional[jax.Array] = None,
               noise: Optional[NoiseConfig] = None,
               sharded: bool = False,
               seg: Optional[jnp.ndarray] = None,
               nids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """One planned layer end-to-end: im2col (conv), tile schedule,
    activation, pooling, and the reshape back to the next layer's view.

    `seg`/`nids` are per *batch sample* (B,) segment and noise-identity
    ids; a conv layer's im2col expansion repeats them across the sample's
    out_h*out_w GEMM rows (plus an intra-sample counter for the noise
    draws), a dense layer uses them as-is."""
    g = lp.spec.conv
    with jax.named_scope("cim.im2col"):
        if g is not None:
            if x.ndim != 4 or x.shape[1:] != g.spatial_in:
                raise ValueError(
                    f"conv layer expects (B, {g.h}, {g.w}, {g.c_in}) "
                    f"activations, got {x.shape}")
            b = x.shape[0]
            rep = g.out_h * g.out_w
            x2 = im2col_patches(x, g).reshape(b * rep, lp.spec.k)
            seg_rows = None if seg is None else jnp.repeat(seg, rep)
            nid_rows = None if nids is None else jnp.repeat(nids, rep)
            sub_rows = (None if nids is None else
                        jnp.tile(jnp.arange(rep, dtype=jnp.int32), b))
        else:
            x2 = x.reshape(x.shape[0], -1)    # conv -> dense flatten (NHWC)
            if x2.shape[-1] != lp.spec.k:
                raise ValueError(f"dense layer expects {lp.spec.k} "
                                 f"features, got {x2.shape[-1]} from "
                                 f"{x.shape}")
            seg_rows, nid_rows, sub_rows = seg, nids, None
    y = _layer_tiles(lp, bind, x2, cfg, matmul=matmul, key=key,
                     noise=noise, sharded=sharded, seg_rows=seg_rows,
                     nid_rows=nid_rows, sub_rows=sub_rows)
    with jax.named_scope("cim.epilogue"):
        if g is not None:
            y = y.reshape(b, g.out_h, g.out_w, g.c_out)
        if lp.pool > 1:
            y = jax.lax.reduce_window(
                y, -jnp.inf, jax.lax.max, (1, lp.pool, lp.pool, 1),
                (1, lp.pool, lp.pool, 1), "VALID")
        return y


def _kernel_matmul(lp: LayerPlan, cfg: EngineConfig):
    # under noise the kernel dispatches in raw-dp mode; the noise ADC
    # epilogue in _tile_schedule owns the conversion
    fuse = not cfg.noise.enabled
    # per-layer tuned blocks (autotuner winners) override the config-wide
    # defaults; the kernel is bit-identical at any block size
    bm, bn, bk = lp.blocks if lp.blocks is not None \
        else (cfg.bm, cfg.bn, cfg.bk)

    def matmul(xq, wqt, gamma_t, beta_t, g0):
        # variant cache keyed on the dispatched call's geometry (a row
        # tile's K x the local column extent): the per-device extents of
        # a sharded schedule get fitted block sizes, not full-width padding
        fn = kops.kernel_variant_for_tile(
            lp.precision, xq.shape[0], xq.shape[1], wqt.shape[1],
            bm=bm, bn=bn, bk=bk, fuse_adc=fuse)
        return fn(xq, wqt, gamma_t, beta_t, g0)
    return matmul


def _reference_matmul(lp: LayerPlan, cfg: EngineConfig):
    from repro.kernels.cim_mbiw.ref import cim_matmul_ref

    if cfg.noise.enabled:
        def matmul(xq, wqt, gamma_t, beta_t, g0):
            # raw integer dp: the shared noise ADC epilogue runs outside,
            # so kernel and reference stay bit-exact under a common key
            return xq.astype(jnp.int32) @ wqt.astype(jnp.int32)
        return matmul

    def matmul(xq, wqt, gamma_t, beta_t, g0):
        # the shared oracle keeps the ADC floor expression in float-op
        # lockstep with the kernel epilogue (bit-exactness contract)
        return cim_matmul_ref(xq, wqt, gamma_t, beta_t, g0=g0,
                              r_out=lp.spec.r_out)
    return matmul


def _forward(plan: NetworkPlan, binds: Sequence[Dict[str, jnp.ndarray]],
             x: jnp.ndarray, reference: bool,
             key: Optional[jax.Array] = None,
             noise: Optional[NoiseConfig] = None,
             m_valid: Optional[jnp.ndarray] = None,
             seg: Optional[jnp.ndarray] = None,
             nids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    if plan.cfg.noise.enabled and key is None:
        raise ValueError(
            "noise-injected engine run requires a PRNG key: pass key= to "
            "run_network/CIMInferenceEngine.__call__ (or plan with "
            "noise=NO_NOISE for the deterministic deployed path)")
    g0 = plan.layers[0].spec.conv
    if g0 is not None:
        if x.ndim < 4 or x.shape[-3:] != g0.spatial_in:
            raise ValueError(
                f"input shape {x.shape} != first conv layer's "
                f"(..., {g0.h}, {g0.w}, {g0.c_in})")
        lead = x.shape[:-3]
        with jax.named_scope("cim.act_quant"):
            xc = x.reshape((-1,) + x.shape[-3:]).astype(jnp.float32)
    else:
        k0 = plan.layers[0].spec.k
        if x.shape[-1] != k0:
            raise ValueError(
                f"input width {x.shape[-1]} != first layer's k={k0}")
        lead = x.shape[:-1]
        with jax.named_scope("cim.act_quant"):
            xc = x.reshape((-1, x.shape[-1])).astype(jnp.float32)
    noisy = noise is not None
    sharded = (not reference) and plan.cfg.sharding is not None
    if seg is not None and seg.shape[0] != xc.shape[0]:
        raise ValueError(f"segments extent {seg.shape[0]} != canonical "
                         f"batch extent {xc.shape[0]}")
    if nids is not None and nids.shape[0] != xc.shape[0]:
        raise ValueError(f"noise_ids extent {nids.shape[0]} != canonical "
                         f"batch extent {xc.shape[0]}")
    for i, (lp, bind) in enumerate(zip(plan.layers, binds)):
        if m_valid is not None:       # batch-bucketed run: re-pin pad rows
            with jax.named_scope("cim.act_quant"):
                xc = _mask_pad_rows(xc, m_valid)
        mk = _reference_matmul if reference else _kernel_matmul
        lkey = jax.random.fold_in(key, i) if noisy else None
        xc = _run_layer(lp, bind, xc, plan.cfg, matmul=mk(lp, plan.cfg),
                        key=lkey, noise=noise, sharded=sharded, seg=seg,
                        nids=nids)
    with jax.named_scope("cim.epilogue"):
        return xc.reshape(lead + xc.shape[1:])


# ---------------------------------------------------------------------------
# grouped calls: one layer shape, G weight sets, one kernel call a row tile
# ---------------------------------------------------------------------------

def group_block_rows(cfg: EngineConfig) -> int:
    """Rows of one block of a grouped call's layout: every block of this
    many GEMM rows belongs to one group (the kernel's row block)."""
    return cfg.bm


@functools.lru_cache(maxsize=None)
def _grouped_plan(spec: mapping.LayerSpec, cfg: EngineConfig) -> LayerPlan:
    return plan_layer(spec, cfg)


def _block_vectors(row_group: jnp.ndarray, n_groups: int, bm: int):
    """(block_group, block_src, block_live) of a grouped row layout.

    A block is live when its first row is (a group's rows start at a block
    boundary and fill it from the top).  An empty block takes the group
    and the input block of the nearest live block before it, so the kernel
    fetches no new operand for it."""
    first = row_group.reshape(-1, bm)[:, 0]
    live = first < n_groups
    idx = jnp.arange(first.shape[0], dtype=jnp.int32)
    src = jnp.maximum(jax.lax.cummax(jnp.where(live, idx, -1), axis=0), 0)
    grp = jnp.minimum(first[src], n_groups - 1)
    return grp, src, live.astype(jnp.int32)


def _grouped_tile_schedule(lp: LayerPlan, q_rows: jnp.ndarray,
                           zp_blk: jnp.ndarray, wqq: jnp.ndarray,
                           gamma: jnp.ndarray, beta: jnp.ndarray,
                           blocks: Tuple[jnp.ndarray, ...], bm: int, *,
                           matmul, nctx: Optional[_LayerNoise] = None
                           ) -> jnp.ndarray:
    """`_tile_schedule` over a grouped layout: `wqq` (G, K, n_pad),
    `gamma`/`beta` (G, n_pad), `zp_blk` (B,) the zero-point of each row
    block's group.  Every per-column expression is `_tile_schedule`'s,
    applied with the row block's group parameters, so a group's rows get
    the bits one ungrouped call over them would.  `nctx` holds per-block
    offsets (B, 1, n_pad) and the thermal field per row tile laid out
    over the rows (KT, B, bm, n_pad)."""
    grp = blocks[0]
    n_blocks = grp.shape[0]
    mid = 2.0 ** (lp.spec.r_out - 1)
    g0 = lp.g0
    with jax.named_scope("cim.recombine"):
        gain = rounding_barrier(gamma * g0)
        gain_b, beta_b, gamma_b = gain[grp], beta[grp], gamma[grp]
        acc = jnp.zeros((n_blocks, bm, wqq.shape[2]), jnp.float32)
    for ki, (ks, ksz) in enumerate(lp.k_slices):
        ke = ks + ksz
        with jax.named_scope("cim.zp_fold"):
            zp_dp = zp_blk[:, None] * jnp.sum(wqq[:, ks:ke], axis=1)[grp]
            beta_eff = beta_b + rounding_barrier(gain_b * zp_dp)
        with jax.named_scope("cim.planes"):
            out = matmul(q_rows[:, ks:ke], wqq[:, ks:ke], gamma_b,
                         beta_eff, g0, *blocks)
        out = out.reshape(n_blocks, bm, -1)
        if nctx is None:
            codes = out
        else:
            with jax.named_scope("cim.noise"):
                codes = _noise_adc_code(lp, out, gamma_b[:, None],
                                        beta_eff[:, None], nctx,
                                        nctx.thermal[ki])
        with jax.named_scope("cim.recombine"):
            acc = acc + (codes.astype(jnp.float32) + 0.5 - mid
                         - beta_b[:, None]) / gain_b[:, None]
    return acc.reshape(n_blocks * bm, -1)


def _grouped_noise(lp: LayerPlan, cfg: EngineConfig, noise: NoiseConfig,
                   gamma_p: jnp.ndarray, key: jax.Array, group_rows: int,
                   bm: int) -> _LayerNoise:
    """Per-group noise contexts, laid out over a grouped call of equal
    groups (`group_rows` live rows each, padded to whole blocks): group g
    draws what its own one-layer program would with key fold_in(key, g)."""
    r_pad = -(-group_rows // bm) * bm
    ctx = [_layer_noise(lp, cfg, noise, gamma_p[g],
                        jax.random.fold_in(jax.random.fold_in(key, g), 0),
                        group_rows)
           for g in range(gamma_p.shape[0])]
    per = r_pad // bm

    def blocks(v):                       # (G, n_pad) -> (B, 1, n_pad)
        return jnp.repeat(jnp.stack(v), per, axis=0)[:, None, :]

    # (G, KT, NT, R, tile_n) -> per row tile (B, bm, NT * tile_n)
    th = jnp.stack([c.thermal for c in ctx])
    g, kt, nt, r, tn = th.shape
    th = jnp.transpose(th, (1, 0, 3, 2, 4)).reshape(kt, g, r, nt * tn)
    th = _pad_dim(th, 2, r_pad).reshape(kt, g * per, bm, nt * tn)
    return _LayerNoise(offset_codes=blocks([c.offset_codes for c in ctx]),
                       droop_codes=blocks([c.droop_codes for c in ctx]),
                       gain_mult=ctx[0].gain_mult, thermal=th)


def _grouped_kernel_matmul(lp: LayerPlan, cfg: EngineConfig, bm: int):
    fuse = not cfg.noise.enabled

    def matmul(xq, wq, gamma_b, beta_b, g0, grp, src, live):
        return kops.cim_matmul_grouped(
            xq, wq, gamma_b, beta_b, grp, src, live, r_in=lp.spec.r_in,
            r_out=lp.spec.r_out, g0=g0, bm=bm, bn=cfg.bn, bk=cfg.bk,
            fuse_adc=fuse)
    return matmul


def _grouped_reference_matmul(lp: LayerPlan, cfg: EngineConfig, bm: int):
    from repro.kernels.cim_mbiw.ref import cim_matmul_ref

    def matmul(xq, wq, gamma_b, beta_b, g0, grp, src, live):
        xb = xq.reshape(grp.shape[0], bm, -1)
        wb = wq[grp]
        if cfg.noise.enabled:
            out = jnp.einsum("bmk,bkn->bmn", xb.astype(jnp.int32),
                             wb.astype(jnp.int32))
        else:
            out = jax.vmap(lambda x, w, g, b: cim_matmul_ref(
                x, w, g, b, g0=g0, r_out=lp.spec.r_out))(
                    xb, wb, gamma_b, beta_b)
        out = jnp.where(live[:, None, None] != 0, out, 0)
        return out.reshape(xq.shape[0], -1)
    return matmul


def group_ranges(row_min: jnp.ndarray, row_max: jnp.ndarray,
                 row_group: jnp.ndarray, n_groups: int, r_in: int,
                 eps: float = 1e-8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(zero, scale), each (G + 1,): the activation range of every group
    of a grouped layout from its rows' minima and maxima, exactly as
    quantize_act's segment path takes it (padding rows, group G, form a
    segment of their own)."""
    from repro.core.quantization import _static_reciprocal
    with jax.named_scope("cim.act_quant"):
        lo = jax.ops.segment_min(row_min, row_group,
                                 num_segments=n_groups + 1)
        hi = jax.ops.segment_max(row_max, row_group,
                                 num_segments=n_groups + 1)
        scale = jnp.maximum(hi - lo, eps) * _static_reciprocal(
            2.0 ** r_in - 1.0)
        return lo, scale


def grouped_layer(spec: mapping.LayerSpec, cfg: EngineConfig, params,
                  x: jnp.ndarray, row_group: jnp.ndarray, *,
                  ranges: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                  reference: bool = False, key: Optional[jax.Array] = None,
                  group_rows: int = 0) -> jnp.ndarray:
    """One layer shape over G weight sets in one kernel call a row tile.

    Args:
      spec: the (K, N) layer and its precision (spec.m is not used).
      cfg: execution config; its sharding is not used (a grouped call runs
        on one device).
      params: {"w" (G, K, N), "abn_log_gamma" (G, N), "abn_beta" (G, N)}.
      x: (M, K) float rows, M a multiple of `group_block_rows(cfg)`, laid
        out in blocks of one group each; a group's rows start at a block
        boundary.
      row_group: (M,) int32, each row's group, or G for a padding row.
      ranges: optional (zero, scale), each (G + 1,), the groups'
        activation ranges (`group_ranges`) when they span more rows than
        this call holds: a layout served in chunks quantizes every chunk
        as the whole layout would.  None takes them over x's rows.
      reference: run the pure-jnp oracle of the same schedule.
      key, group_rows: the noise model's key (required when cfg.noise is
        on) and the live rows of every group; noise needs equal groups,
        each `group_rows` rows from its first block.
    Returns:
      (M, N) float32.  Activation ranges are taken per group over its live
      rows only (quantize_act segments; padding rows form a segment of
      their own), so each group's rows get the bits of one ungrouped call
      over them; a padding row's output is meaningless.
    """
    from repro.core.quantization import quantize_act
    lp = _grouped_plan(spec, cfg.replace(sharding=None))
    n_groups = params["w"].shape[0]
    bm = group_block_rows(cfg)
    noise = cfg.noise if cfg.noise.enabled else None
    if noise is not None and (key is None or group_rows < 1):
        raise ValueError("a noisy grouped call needs a key and equal "
                         "groups (group_rows)")
    binds = jax.vmap(lambda p: bind_layer(lp, p, cfg))(params)
    blocks = _block_vectors(row_group, n_groups, bm)
    with jax.named_scope("cim.act_quant"):
        if ranges is None:
            aq = quantize_act(x.astype(jnp.float32), spec.r_in,
                              segment_ids=row_group,
                              num_segments=n_groups + 1)
        else:
            zero, scale = ranges
            aq = quantize_act(x.astype(jnp.float32), spec.r_in,
                              zero=zero[row_group][:, None],
                              scale=scale[row_group][:, None])
        zp = jnp.asarray(aq.zero / aq.scale, jnp.float32)
        # a live block's first row is live: its zero-point is the group's
        zp_blk = zp.reshape(-1, bm)[:, 0]
    with jax.named_scope("cim.noise"):
        nctx = (_grouped_noise(lp, cfg, noise, binds["gamma_p"], key,
                               group_rows, bm)
                if noise is not None else None)
    mk = _grouped_reference_matmul if reference else _grouped_kernel_matmul
    dp_hat = _grouped_tile_schedule(
        lp, aq.q, zp_blk, binds["wqq"], binds["gamma_p"], binds["beta_p"],
        blocks, bm, matmul=mk(lp, cfg, bm), nctx=nctx)
    with jax.named_scope("cim.epilogue"):
        n, nb = spec.n, blocks[0].shape[0]
        y = (dp_hat[:, :n].reshape(nb, bm, n) * aq.scale.reshape(nb, bm, 1)
             * binds["w_scale"][blocks[0]][:, None, :])
        return y.reshape(nb * bm, n)


@functools.partial(jax.jit, static_argnames=("plan", "bound", "reference"))
def _exec_jit(plan: NetworkPlan, payload, x: jnp.ndarray, m_valid,
              key, noise, seg, nids, bound: bool,
              reference: bool) -> jnp.ndarray:
    """The one jitted executable behind every engine entry point.

    `payload` is the per-layer parameter list (bound=False: weight binding
    runs inside this graph, the legacy per-call behaviour) or a tuple of
    bind_layer products (bound=True: weight quantization left the per-call
    path at CIMProgram.bind time).  `m_valid` (traced) marks the live batch
    extent of a bucket-padded run, or None for exact-shape dispatch.
    `seg`/`nids` (traced, (B,) int32 or None) are the per-sample segment
    ids of segment-wise activation quantization and the per-sample noise
    identity ids of identity-keyed thermal draws."""
    TRACE_COUNT["n"] += 1            # trace-time side effect: 1 per compile
    if bound:
        binds = list(payload)
    else:
        if len(payload) != len(plan.layers):
            raise ValueError(f"{len(payload)} param dicts for "
                             f"{len(plan.layers)} planned layers")
        binds = [bind_layer(lp, p, plan.cfg)
                 for lp, p in zip(plan.layers, payload)]
    return _forward(plan, binds, x, reference=reference, key=key,
                    noise=noise, m_valid=m_valid, seg=seg, nids=nids)


def _dispatch_noise(plan: NetworkPlan,
                    noise: Optional[NoiseConfig]) -> Optional[NoiseConfig]:
    """Resolve the run's noise operating point as a *traced* operand.

    None -> the planned point (or no noise at all under NO_NOISE plans);
    an explicit NoiseConfig overrides the planned numeric terms at dispatch
    time without recompiling, but must agree on `enabled` (that flag
    switches the static fuse_adc kernel path — replan to change modes)."""
    base = plan.cfg.noise
    if noise is None:
        return base if base.enabled else None
    if bool(noise.enabled) != bool(base.enabled):
        raise ValueError(
            f"noise override enabled={noise.enabled} conflicts with the "
            f"planned enabled={base.enabled}; replan with "
            "EngineConfig(noise=...) to switch modes")
    return noise if noise.enabled else None


def init_network_params(plan: NetworkPlan, key: jax.Array) -> Params:
    """Distribution-aware per-layer parameters for a planned network
    (core/cim_layers init, one {"w", "abn_log_gamma", "abn_beta"} dict per
    layer in plan order)."""
    from repro.core.cim_layers import CIMConfig, init_cim_linear
    cfg = plan.cfg
    params = []
    for lp in plan.layers:
        key, sub = jax.random.split(key)
        lcfg = CIMConfig(
            r_in=lp.spec.r_in, r_w=lp.spec.r_w, r_out=lp.spec.r_out,
            adaptive_swing=cfg.adaptive_swing,
            gamma_bits=cfg.gamma_bits, max_gamma=cfg.max_gamma,
            macro=cfg.macro)
        params.append(init_cim_linear(sub, lp.spec.k, lp.spec.n, cfg=lcfg))
    return params


def run_network(plan: NetworkPlan, params: Params, x: jnp.ndarray,
                key: Optional[jax.Array] = None,
                noise: Optional[NoiseConfig] = None, *,
                segments: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Execute the planned schedule through the Pallas kernel variants.

    .. deprecated:: this is the per-call entry point; it keeps working
       unchanged (backed by the program cache of runtime/program.py, so
       repeated calls at one plan reuse the compiled executable) but new
       code should compile once via `compile_program` and serve through
       the returned CIMProgram/BoundProgram.

    Args:
      plan: the (jit-static) NetworkPlan; with plan.cfg.sharding set the
        schedule dispatches across the device mesh via shard_map.
      params: one {"w", "abn_log_gamma", "abn_beta"} dict per layer.
      x: (..., K0) real-valued activations for a dense-first plan, or
        (..., H, W, C_in) NHWC images for a conv-first plan.
      key: PRNG key seeding the noise model (required when the plan has
        noise enabled, ignored under NO_NOISE).
      noise: optional NoiseConfig whose *numeric* terms override the
        planned operating point at dispatch time — traced scalars, so a
        sweep across operating points shares one compile.
      segments: optional (B,) int32 per-sample segment ids — activation
        quantization reduces per segment instead of batch-globally, so
        samples in different segments never share dynamic swing state
        (the serving-side per-request isolation primitive).
    Returns:
      (..., N_last) activations — or (..., out_h, out_w, C_out) if the
      last layer is a conv.
    """
    _warn_legacy_entry("run_network")
    from repro.runtime.program import program_for_plan
    return program_for_plan(plan).run(params, x, key, noise,
                                      segments=segments)


def run_network_reference(plan: NetworkPlan, params: Params, x: jnp.ndarray,
                          key: Optional[jax.Array] = None,
                          noise: Optional[NoiseConfig] = None) -> jnp.ndarray:
    """Pure-jnp digital oracle of the identical schedule (bit-exact with
    the kernel path — including under noise, where both share the same
    post-matmul ADC epilogue and pre-drawn per-tile thermal fields, and
    including sharded plans, which the oracle executes serially)."""
    from repro.runtime.program import program_for_plan
    return program_for_plan(plan).run(params, x, key, noise,
                                      reference=True)


class CIMInferenceEngine:
    """Thin compatibility wrapper over a compiled `CIMProgram`.

    Construction routes through the global program cache of
    runtime/program.py, so two engines over equal (specs, cfg) share one
    plan and one executable cache; every call dispatches the cached
    jit-compiled schedule (single-device or sharded per cfg.sharding).
    New code should hold the program directly: `engine.compile()` (or
    `compile_program(specs, cfg)`) returns it."""

    def __init__(self, specs: Sequence[mapping.LayerSpec],
                 cfg: EngineConfig = EngineConfig(),
                 activations: Optional[Sequence[str]] = None,
                 pools: Optional[Sequence[int]] = None):
        from repro.runtime.program import compile_program
        self.cfg = cfg
        self.program = compile_program(specs, cfg, activations=activations,
                                       pools=pools)

    @property
    def plan(self) -> NetworkPlan:
        """The backing program's (jit-static) NetworkPlan."""
        return self.program.plan

    def compile(self):
        """The backing CIMProgram — the plan-once/serve-many artifact
        (bind weights with .bind(params), serve ragged batches with
        .serve/.serve_batch)."""
        return self.program

    def init_params(self, key: jax.Array) -> Params:
        """Distribution-aware per-layer parameters (core/cim_layers init)."""
        return init_network_params(self.plan, key)

    def __call__(self, params: Params, x: jnp.ndarray,
                 key: Optional[jax.Array] = None,
                 noise: Optional[NoiseConfig] = None) -> jnp.ndarray:
        """Exact-shape dispatch of the compiled schedule (legacy per-call
        API; prefer engine.compile() + program.bind(params).serve(x))."""
        _warn_legacy_entry("CIMInferenceEngine.__call__")
        return self.program.run(params, x, key, noise)

    def reference(self, params: Params, x: jnp.ndarray,
                  key: Optional[jax.Array] = None,
                  noise: Optional[NoiseConfig] = None) -> jnp.ndarray:
        """The pure-jnp digital oracle of the same plan (bit-exact with
        __call__ at every precision, clean or under a common key)."""
        return self.program.run(params, x, key, noise, reference=True)

    def monte_carlo(self, params: Params, x: jnp.ndarray, key: jax.Array,
                    n_trials: int,
                    noise: Optional[NoiseConfig] = None) -> jnp.ndarray:
        """Batched seeded noise trials: (n_trials, *engine(params, x).shape).

        Splits `key` into one subkey per trial and stacks the outputs;
        every trial reuses the jit cache of the planned schedule, so the
        cost is n_trials dispatches, not n_trials compiles (`noise` points
        share the compile too — traced operands).  Deterministic for a
        fixed key; requires a noise-enabled plan."""
        if not self.cfg.noise.enabled:
            raise ValueError("monte_carlo requires EngineConfig(noise=...) "
                             "with noise enabled")
        if n_trials < 1:
            raise ValueError(f"n_trials must be >= 1, got {n_trials}")
        keys = jax.random.split(key, n_trials)
        return jnp.stack([self.program.run(params, x, k, noise)
                          for k in keys])

    def perf_report(self, **kw):
        """Per-layer + aggregate cycle/energy estimates (perfmodel);
        sharded plans add per-device macro_evals and parallel efficiency,
        and the report echoes the backing program's compile/bucket stats
        under "program"."""
        from repro.perfmodel.macro_perf import schedule_report
        return schedule_report(self.plan, program=self.program, **kw)
