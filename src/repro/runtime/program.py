"""Compiled CIM programs: plan once, serve many (the deployment API).

The IMAGINE macro's whole economics are amortization — weights stay
resident in the 1152x256 array while input-serial activations stream
through — so the runtime should pay planning and XLA tracing once per
*program*, not once per call.  This module is that artifact layer:

    prog   = compile_program(specs, EngineConfig(...))   # plan + cache once
    params = prog.init_params(jax.random.PRNGKey(0))
    bound  = prog.bind(params)          # weights pre-quantized & packed
    y      = bound.serve(x)             # ragged batch -> bucketed dispatch
    ys     = bound.serve_batch([x1, x2, x3])   # multi-request serving
    prog.stats()                        # plans/compiles/bucket hit-miss

Three amortization levers, each observable through `CIMProgram.stats()`:

* **Plan cache** — `compile_program` keys a module-level cache on
  (specs, cfg, activations, pools, buckets): equal programs share one
  `NetworkPlan` (planned exactly once — engine.PLAN_COUNT counts) and one
  executable cache.  `core/cim_layers` engine mode and the serving launcher
  enter the engine exclusively through this cache.
* **Batch bucketing** — `serve` pads the leading batch axis up to a
  power-of-two ladder rung (`BatchBuckets`), so arbitrary request sizes hit
  a bounded set of jit executables instead of one compile per batch size.
  Padding rows are copies of row 0 and are re-pinned before every layer
  (engine._mask_pad_rows), which keeps the dynamic activation-quantization
  statistics — and therefore every live-row bit — identical to an unpadded
  run, clean *and* under a fixed noise key (thermal draws are generated in
  fixed global row blocks, invariant to the padded extent).
* **Weight binding** — `bind(params)` runs engine.bind_network once
  (weight quantization to the odd-integer grid, ABN gamma evaluation,
  col-tile padding), removing the weight-side work from the per-call graph;
  a `BoundProgram` serves without ever touching the fp32 masters again.

Sharded plans (EngineConfig.sharding) serve through the same API — the
bucket executables dispatch the multi-macro shard_map schedule, and the
bucket-padding contract composes with both shard kinds bit-exactly.

Units/shapes follow runtime/engine.py; everything here is orchestration —
no numerics of its own.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import mapping
from repro.core.noise_model import NoiseConfig
from repro.runtime import engine as rt


@dataclasses.dataclass(frozen=True)
class BatchBuckets:
    """Power-of-two ladder of batch bucket sizes.

    A request of leading batch extent m dispatches at the smallest rung
    `min_bucket * 2^i >= m`; with `max_bucket` set the ladder is capped
    there and larger requests pad to the next *multiple* of max_bucket
    (bounded compile count either way, padding waste < 2x).

    Attributes:
      min_bucket: smallest rung (>= 1).
      max_bucket: ladder cap; 0 means uncapped (pure power-of-two ladder).
    """
    min_bucket: int = 1
    max_bucket: int = 0

    def __post_init__(self):
        if self.min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, got "
                             f"{self.min_bucket}")
        if self.max_bucket and self.max_bucket < self.min_bucket:
            raise ValueError(
                f"max_bucket {self.max_bucket} < min_bucket "
                f"{self.min_bucket}")

    def bucket_for(self, m: int) -> int:
        """The padded batch extent a request of `m` rows dispatches at."""
        if m < 1:
            raise ValueError(f"batch extent must be >= 1, got {m}")
        cap = self.max_bucket
        if cap and m > cap:
            return cap * -(-m // cap)        # beyond the ladder: cap grid
        b = self.min_bucket
        while b < m:
            b *= 2
        return min(b, cap) if cap else b

    def ladder(self, max_m: int) -> Tuple[int, ...]:
        """Every distinct bucket requests of size 1..max_m can land on
        (the compile-count bound batch bucketing guarantees)."""
        return tuple(sorted({self.bucket_for(m)
                             for m in range(1, max_m + 1)}))


DEFAULT_BUCKETS = BatchBuckets()

_STAT_KEYS = ("plans_built", "executables_compiled", "bucket_hits",
              "bucket_misses", "run_calls", "serve_calls")

# stride separating per-request noise-id ranges (request_noise_ids):
# 2^20 rows per request before ids collide — collisions would only
# correlate two rows' thermal draws, never break per-request determinism
NOISE_ID_STRIDE = 1 << 20


def request_noise_ids(request_index: int, rows: int) -> jnp.ndarray:
    """Canonical per-row noise-identity ids of one request.

    `(request_index, row)` maps to `request_index * NOISE_ID_STRIDE + row`
    (int32).  Both the fused serve_batch(isolate=True) path and a solo
    per-request serve must key thermal draws on the *same* ids for noise
    runs to be bit-identical — use this helper on both sides.

    Raises ValueError when the range would leave int32: with the default
    stride that is `request_index >= 2048`, where the old arithmetic
    silently wrapped into another request's id range (x64 is disabled, so
    the ids must genuinely fit int32)."""
    if request_index < 0:
        raise ValueError(f"request_index must be >= 0, got {request_index}")
    if rows < 1:
        raise ValueError(f"rows must be >= 1, got {rows}")
    base = request_index * NOISE_ID_STRIDE       # python int: no wrap
    if base + rows - 1 > 0x7FFFFFFF:
        raise ValueError(
            f"request_noise_ids({request_index}, {rows}) spans "
            f"[{base}, {base + rows}) which overflows int32; at stride "
            f"{NOISE_ID_STRIDE} only request indices < "
            f"{(0x7FFFFFFF + 1) // NOISE_ID_STRIDE} are representable")
    return (jnp.arange(rows, dtype=jnp.int32)
            + jnp.int32(base))


# the trace-signature fields an executable cache key must discriminate;
# `executable_key` is the single constructor both dispatch paths and the
# cimcheck recompile-hazard pass (analysis/recompile.py) share, so a field
# added to the jit signature but dropped from the key is statically visible
EXEC_KEY_FIELDS = ("kind", "extent", "noise", "keyed", "devices", "bound",
                   "reference", "segmented", "identity", "point")


def executable_key(kind: str, extent: int, *, noise: bool, keyed: bool,
                   devices: int, bound: bool, reference: bool,
                   segmented: bool, identity: bool,
                   point: str = "") -> tuple:
    """The cache key of one executable trace signature.

    Mirrors the jit static/presence signature of `_exec_jit`: dispatch
    kind ("exact"/"bucket") and batch extent, plus every operand-presence
    flag that changes the traced graph (noise operands, PRNG key, device
    mesh, bound params, reference oracle, segment ids, noise-identity
    ids), plus the serving operating-point tag (`point`, "" for the base
    point) — distinct precision-ladder rungs execute distinct plans, so
    the point must discriminate or the key would report a hit while jit
    retraces.  Keep in sync with EXEC_KEY_FIELDS."""
    return (kind, int(extent), bool(noise), bool(keyed), int(devices),
            bool(bound), bool(reference), bool(segmented), bool(identity),
            str(point))


@functools.partial(jax.jit, static_argnames=("plan",))
def _bind_jit(plan: rt.NetworkPlan, params: rt.Params):
    return list(rt.bind_network(plan, list(params)))


def _pad_rows(bucket: int, xc: jnp.ndarray, seg, nid):
    """Pad the canonical batch up to `bucket` rows with copies of row 0.

    Pad ids mirror the pad rows: the pad rows stay duplicates inside row
    0's segment, so no segment's min/max can move and live rows stay
    bit-exact."""
    with jax.named_scope("cim.act_quant"):
        m = xc.shape[0]
        pad = jnp.broadcast_to(xc[:1], (bucket - m,) + xc.shape[1:])
        xc = jnp.concatenate([xc, pad], axis=0)
        if seg is not None:
            seg = jnp.concatenate(
                [seg, jnp.broadcast_to(seg[:1], (bucket - m,))])
        if nid is not None:
            nid = jnp.concatenate(
                [nid, jnp.broadcast_to(nid[:1], (bucket - m,))])
        return xc, seg, nid


class CIMProgram:
    """An immutable, hashable compiled CIM inference artifact.

    Owns one `NetworkPlan` (planned exactly once) plus a cache of jitted
    executables keyed on (dispatch kind, batch bucket, noise on/off, key
    presence, device count, bound, reference) — the fields that change the
    traced graph.  Two dispatch styles:

    * `run(params, x, ...)` — exact-shape dispatch, the legacy
      run_network semantics (one executable per distinct batch extent);
    * `serve(params, x, ...)` / `bind(params).serve(x, ...)` — batch-
      bucketed dispatch: x pads up the `BatchBuckets` ladder, runs, and
      slices back, bit-exact with an exact-shape run of the same inputs.

    Programs are hashable on (plan, buckets) — the executable/stat caches
    are bookkeeping, not identity.
    """

    __slots__ = ("_plan", "_buckets", "_executables", "_stats")

    def __init__(self, plan: rt.NetworkPlan,
                 buckets: BatchBuckets = DEFAULT_BUCKETS):
        object.__setattr__(self, "_plan", plan)
        object.__setattr__(self, "_buckets", buckets)
        object.__setattr__(self, "_executables", {})
        object.__setattr__(self, "_stats",
                           {k: 0 for k in _STAT_KEYS} | {"plans_built": 1})

    def __setattr__(self, name, value):
        raise AttributeError("CIMProgram is immutable")

    def __hash__(self):
        return hash((self._plan, self._buckets))

    def __eq__(self, other):
        return (type(other) is CIMProgram and self._plan == other._plan
                and self._buckets == other._buckets)

    def __repr__(self):
        lay = len(self._plan.layers)
        return (f"CIMProgram({lay} layers, buckets={self._buckets}, "
                f"executables={len(self._executables)})")

    @property
    def plan(self) -> rt.NetworkPlan:
        """The jit-static NetworkPlan this program executes."""
        return self._plan

    @property
    def buckets(self) -> BatchBuckets:
        """The batch-bucket ladder `serve` pads requests onto."""
        return self._buckets

    @property
    def cfg(self) -> rt.EngineConfig:
        """The plan's shared EngineConfig."""
        return self._plan.cfg

    def init_params(self, key: jax.Array) -> rt.Params:
        """Distribution-aware per-layer parameters (core/cim_layers init)."""
        return rt.init_network_params(self._plan, key)

    def bind(self, params: rt.Params) -> "BoundProgram":
        """Pre-quantize/pack the weights: the per-call path never touches
        the fp32 masters again.  Returns a BoundProgram closed over the
        engine.bind_network products (odd-integer weight codes, dequant
        scales, padded ABN gain/offset)."""
        return BoundProgram(self, tuple(_bind_jit(self._plan, list(params))))

    # -- dispatch ----------------------------------------------------------

    def _devices(self) -> int:
        sh = self._plan.cfg.sharding
        return sh.resolve_devices() if sh is not None else 1

    def _canon(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, Tuple[int, ...]]:
        """Collapse leading dims to one canonical batch axis (so equal
        batch extents share one executable regardless of lead shape)."""
        x = jnp.asarray(x)
        g = self._plan.layers[0].spec.conv
        if g is not None:
            if x.ndim < 4 or x.shape[-3:] != g.spatial_in:
                raise ValueError(
                    f"input shape {x.shape} != first conv layer's "
                    f"(..., {g.h}, {g.w}, {g.c_in})")
            return x.reshape((-1,) + x.shape[-3:]), x.shape[:-3]
        k0 = self._plan.layers[0].spec.k
        if x.ndim < 1 or x.shape[-1] != k0:
            raise ValueError(
                f"input width {x.shape[-1] if x.ndim else 0} != first "
                f"layer's k={k0}")
        return x.reshape((-1, k0)), x.shape[:-1]

    def _canon_rows(self, v, m: int, name: str):
        """Canonicalize an optional per-sample id vector (segments /
        noise_ids) against the collapsed batch extent `m`."""
        if v is None:
            return None
        v = jnp.asarray(v, jnp.int32).reshape(-1)
        if v.shape[0] != m:
            raise ValueError(
                f"{name} has {v.shape[0]} entries for batch extent {m}")
        return v

    def _note_executable(self, key: tuple, bucketed: bool) -> None:
        st = self._stats
        st["serve_calls" if bucketed else "run_calls"] += 1
        if key in self._executables:
            if bucketed:
                st["bucket_hits"] += 1
            return
        self._executables[key] = True
        st["executables_compiled"] += 1
        if bucketed:
            st["bucket_misses"] += 1

    def run(self, params: rt.Params, x: jnp.ndarray,
            key: Optional[jax.Array] = None,
            noise: Optional[NoiseConfig] = None, *,
            segments: Optional[jnp.ndarray] = None,
            noise_ids: Optional[jnp.ndarray] = None,
            reference: bool = False) -> jnp.ndarray:
        """Exact-shape dispatch (run_network semantics, no bucketing): one
        cached executable per distinct batch extent.  `reference=True`
        runs the pure-jnp digital oracle of the same schedule.
        `segments`/`noise_ids` are optional per-sample ids: segment-wise
        activation quantization and identity-keyed noise draws (the
        per-request isolation primitives — see BoundProgram.serve)."""
        nz = rt._dispatch_noise(self._plan, noise)
        xc, lead = self._canon(x)
        seg = self._canon_rows(segments, xc.shape[0], "segments")
        nid = self._canon_rows(noise_ids, xc.shape[0], "noise_ids")
        # the key tuple mirrors the jit trace signature: dispatch kind and
        # key presence both change the traced graph, so they discriminate
        self._note_executable(
            executable_key("exact", xc.shape[0], noise=nz is not None,
                           keyed=key is not None, devices=self._devices(),
                           bound=False, reference=bool(reference),
                           segmented=seg is not None,
                           identity=nid is not None), bucketed=False)
        y = rt._exec_jit(self._plan, list(params), xc, None, key, nz,
                         seg, nid, False, bool(reference))
        return y.reshape(lead + y.shape[1:])

    def serve(self, params: rt.Params, x: jnp.ndarray,
              key: Optional[jax.Array] = None,
              noise: Optional[NoiseConfig] = None, *,
              segments: Optional[jnp.ndarray] = None,
              noise_ids: Optional[jnp.ndarray] = None,
              reference: bool = False, point: str = "") -> jnp.ndarray:
        """Batch-bucketed dispatch with per-call params (weight binding
        stays in the jitted graph — use bind(params).serve(...) to hoist
        it).  Bit-exact with `run` on the same inputs.  `point` tags the
        dispatch with a serving operating-point name (joins the
        executable key; "" is the base point)."""
        return self._serve_padded(list(params), False, x, key, noise,
                                  bool(reference), segments, noise_ids,
                                  point)

    def _serve_padded(self, payload, bound: bool, x: jnp.ndarray,
                      key, noise, reference: bool,
                      segments=None, noise_ids=None,
                      point: str = "") -> jnp.ndarray:
        """The host side of every bucketed dispatch (bucket lookup,
        padding, executable call), inside the `repro.serve` profiler span
        with the live `rows` and the `bucket` as its arguments."""
        with jax.profiler.TraceAnnotation("repro.serve") as span:
            nz = rt._dispatch_noise(self._plan, noise)
            xc, lead = self._canon(x)
            m = xc.shape[0]
            if m < 1:
                raise ValueError("cannot serve an empty batch")
            seg = self._canon_rows(segments, m, "segments")
            nid = self._canon_rows(noise_ids, m, "noise_ids")
            bucket = self._buckets.bucket_for(m)
            span.set_metadata(rows=m, bucket=bucket)
            if bucket > m:
                xc, seg, nid = _pad_rows(bucket, xc, seg, nid)
            self._note_executable(
                executable_key("bucket", bucket, noise=nz is not None,
                               keyed=key is not None, devices=self._devices(),
                               bound=bound, reference=reference,
                               segmented=seg is not None,
                               identity=nid is not None,
                               point=str(point)), bucketed=True)
            y = rt._exec_jit(self._plan, payload, xc,
                             jnp.asarray(m, jnp.int32), key, nz, seg, nid,
                             bound, reference)
            with jax.named_scope("cim.epilogue"):
                return y[:m].reshape(lead + y.shape[1:])

    # -- observability -----------------------------------------------------

    def stats(self) -> Dict[str, int]:
        """Compile/cache counters of this program: plans_built (always 1 —
        the plan is built at compile_program time), executables_compiled
        (distinct trace signatures dispatched: kind, bucket, noise, key
        presence, devices, bound, reference), bucket_hits/bucket_misses
        (serve-path ladder lookups), run_calls/serve_calls."""
        return dict(self._stats)

    def perf_report(self, **kw):
        """perfmodel.schedule_report of the plan, with this program's
        compile/bucket stats echoed under report["program"]."""
        from repro.perfmodel.macro_perf import schedule_report
        return schedule_report(self._plan, program=self, **kw)


class BoundProgram:
    """A CIMProgram closed over pre-quantized weights (the serve-side
    artifact: no fp32 weight masters, no per-call weight quantization).

    `serve(x)` dispatches one request through the batch-bucket ladder;
    `serve_batch([x1, ...])` concatenates requests, serves the fused batch
    once, and splits the results back per request.  By default the fusion
    shares the dynamic activation-quantization statistics across the fused
    batch (exactly like running the concatenated batch through the
    engine) — bit-exact with `serve(concat(requests))`, not with
    per-request serve calls.  `serve_batch(..., isolate=True)` instead
    tags each request as its own quantization segment (segment-wise
    `quantize_act`), making every request bit-identical to serving it
    alone — the contract in-flight batched decode
    (runtime/scheduler.py) is built on."""

    __slots__ = ("program", "_binds")

    def __init__(self, program: CIMProgram, binds: Tuple[Dict, ...]):
        object.__setattr__(self, "program", program)
        object.__setattr__(self, "_binds", binds)

    def __setattr__(self, name, value):
        raise AttributeError("BoundProgram is immutable")

    @property
    def plan(self) -> rt.NetworkPlan:
        """The backing program's NetworkPlan."""
        return self.program.plan

    def serve(self, x: jnp.ndarray, key: Optional[jax.Array] = None,
              noise: Optional[NoiseConfig] = None, *,
              segments: Optional[jnp.ndarray] = None,
              noise_ids: Optional[jnp.ndarray] = None,
              reference: bool = False, point: str = "") -> jnp.ndarray:
        """Bucketed dispatch of one request through the bound weights
        (bit-exact with the unbucketed engine on the same inputs, clean
        and under a fixed noise key).

        `segments` ((B,) int32, optional) switches activation quantization
        to per-segment statistics: samples with different ids never share
        dynamic swing state, so a fused batch is bit-exact with serving
        each segment alone.  `noise_ids` ((B,) int32, optional) keys the
        noise model's thermal draws by sample identity instead of batch
        position (see request_noise_ids) — together they make noisy fused
        serving bit-exact with solo serving under one key.  `point` tags
        the dispatch with the serving operating-point name ("" = base):
        it joins the executable key so precision-ladder rungs never alias
        one cache entry."""
        return self.program._serve_padded(list(self._binds), True, x, key,
                                          noise, bool(reference),
                                          segments, noise_ids, point)

    __call__ = serve

    def reference(self, x: jnp.ndarray, key: Optional[jax.Array] = None,
                  noise: Optional[NoiseConfig] = None, *,
                  segments: Optional[jnp.ndarray] = None,
                  noise_ids: Optional[jnp.ndarray] = None,
                  point: str = "") -> jnp.ndarray:
        """The pure-jnp digital oracle of serve (bit-exact with it)."""
        return self.serve(x, key, noise, segments=segments,
                          noise_ids=noise_ids, reference=True, point=point)

    def serve_batch(self, requests: Sequence[jnp.ndarray],
                    key: Optional[jax.Array] = None,
                    noise: Optional[NoiseConfig] = None, *,
                    isolate: bool = False) -> List[jnp.ndarray]:
        """Multi-request serving: concatenate, bucket-pad, dispatch once
        (through the sharded engine when the plan is sharded), split.

        Args:
          requests: per-request activation arrays, each batch-major with
            the plan's feature shape — (b_i, K0) dense or
            (b_i, H, W, C_in) conv.
          key: PRNG key for noise-enabled plans (one key for the fused
            batch; per-request noise follows each request's row offset —
            or its request_noise_ids identity under `isolate`).
          noise: optional operating-point override (traced — no recompile).
          isolate: per-request numerical isolation.  False (default)
            keeps the legacy fusion semantics — the dynamic activation-
            quantization statistics are shared across the fused batch, so
            the results are bit-exact with `serve(concat(requests))` but
            NOT with per-request serves.  True tags each request as its
            own quantization segment (and, under noise, keys thermal
            draws on request_noise_ids(i, b_i)), making every request's
            rows bit-identical to a solo
            `serve(x_i, key, segments=zeros(b_i),
            noise_ids=request_noise_ids(i, b_i))` call.
        Returns:
          One result array per request, in order, each with its own
          leading b_i.
        """
        if not requests:
            return []
        xs = [jnp.asarray(r) for r in requests]
        feat = xs[0].shape[1:]
        for i, r in enumerate(xs):
            if r.ndim != len(feat) + 1 or r.shape[1:] != feat:
                raise ValueError(
                    f"request {i} shape {r.shape} is not batch-major with "
                    f"feature shape {feat}")
        sizes = [r.shape[0] for r in xs]
        segments = noise_ids = None
        if isolate:
            segments = jnp.concatenate(
                [jnp.full((b,), i, jnp.int32)
                 for i, b in enumerate(sizes)])
            if key is not None:
                noise_ids = jnp.concatenate(
                    [request_noise_ids(i, b)
                     for i, b in enumerate(sizes)])
        y = self.serve(jnp.concatenate(xs, axis=0), key, noise,
                       segments=segments, noise_ids=noise_ids)
        out, s = [], 0
        for b in sizes:
            out.append(y[s:s + b])
            s += b
        return out

    def stats(self) -> Dict[str, int]:
        """The backing program's compile/bucket counters."""
        return self.program.stats()


class SharedInputProgram:
    """N projection heads over one shared input, fused as ONE program.

    A transformer block computes several projections of the *same*
    normalized hidden state — Q/K/V from the attention input, gate/up from
    the MLP input.  On the macro these are columns of one wide GEMM: the
    activations stream through the rows once and every head's columns
    convert in the same ADC pass.  This artifact expresses that: it
    compiles a single (k -> sum(n_i)) layer via `compile_program` (so the
    fused program shares the global plan cache like any other) and serves
    every head from one dispatch.

    Bit-exactness of the per-head slices vs. per-head programs is
    structural, not approximate: activation quantization depends only on
    the shared input, and weight quantization, ABN gamma/beta, the ADC
    epilogue, and dequantization are all per-output-column — concatenating
    heads along the output axis changes no column's arithmetic
    (tests/test_program.py proves the slices bitwise).
    """

    __slots__ = ("program", "heads", "_offsets")

    def __init__(self, program: CIMProgram,
                 heads: Sequence[Tuple[str, int]]):
        heads = tuple((str(name), int(n)) for name, n in heads)
        if len({name for name, _ in heads}) != len(heads):
            raise ValueError(f"duplicate head names in {heads}")
        n_tot = sum(n for _, n in heads)
        if len(program.plan.layers) != 1:
            raise ValueError("shared-input fusion is a single-layer "
                             f"artifact, got {len(program.plan.layers)} "
                             "layers")
        if program.plan.layers[0].spec.n != n_tot:
            raise ValueError(
                f"program n={program.plan.layers[0].spec.n} != "
                f"sum of head widths {n_tot}")
        offsets, s = [], 0
        for _, n in heads:
            offsets.append((s, s + n))
            s += n
        object.__setattr__(self, "program", program)
        object.__setattr__(self, "heads", heads)
        object.__setattr__(self, "_offsets", tuple(offsets))

    def __setattr__(self, name, value):
        raise AttributeError("SharedInputProgram is immutable")

    @classmethod
    def compile(cls, k: int, heads: Sequence[Tuple[str, int]],
                cfg: rt.EngineConfig = rt.EngineConfig(), *,
                r_in: int, r_w: int, m: int = 8,
                buckets: BatchBuckets = DEFAULT_BUCKETS
                ) -> "SharedInputProgram":
        """Compile (through the global program cache) the fused program of
        `heads` — ((name, n_i), ...) projections sharing a width-k input
        at one precision point.  `m` is the planner's batch-extent hint."""
        heads = tuple((str(name), int(n)) for name, n in heads)
        n_tot = sum(n for _, n in heads)
        prog = compile_program(
            (mapping.LayerSpec(m=m, k=int(k), n=n_tot,
                               r_in=r_in, r_w=r_w),),
            cfg, activations=("none",), buckets=buckets)
        return cls(prog, heads)

    @property
    def k(self) -> int:
        """The shared input width."""
        return self.program.plan.layers[0].spec.k

    def init_params(self, key: jax.Array) -> Dict[str, Dict]:
        """Distribution-aware init, split per head: {name: {"w",
        "abn_log_gamma", "abn_beta"}} with w (k, n_i)."""
        (lay,) = list(self.program.init_params(key))
        out = {}
        for (name, _), (s, e) in zip(self.heads, self._offsets):
            out[name] = {"w": lay["w"][:, s:e],
                         "abn_log_gamma": lay["abn_log_gamma"][s:e],
                         "abn_beta": lay["abn_beta"][s:e]}
        return out

    def bind(self, params: Dict[str, Dict]) -> "SharedInputBind":
        """Concatenate the per-head params along the output axis and bind
        once (weight quantization is per-output-column, so the fused bind
        equals the per-head binds column for column)."""
        missing = [name for name, _ in self.heads if name not in params]
        if missing:
            raise ValueError(f"missing head params {missing}")
        for (name, n) in self.heads:
            w = params[name]["w"]
            if w.shape != (self.k, n):
                raise ValueError(
                    f"head {name!r} weight shape {w.shape} != "
                    f"({self.k}, {n})")
        cat = {
            fld: jnp.concatenate(
                [jnp.asarray(params[name][fld]) for name, _ in self.heads],
                axis=-1 if fld == "w" else 0)
            for fld in ("w", "abn_log_gamma", "abn_beta")}
        return SharedInputBind(self, self.program.bind([cat]))

    def stats(self) -> Dict[str, int]:
        """The fused program's compile/bucket counters."""
        return self.program.stats()


class SharedInputBind:
    """A SharedInputProgram closed over bound (pre-quantized) weights:
    `serve(x)` runs the one fused dispatch and returns {head: slice}."""

    __slots__ = ("shared", "bound")

    def __init__(self, shared: SharedInputProgram, bound: BoundProgram):
        object.__setattr__(self, "shared", shared)
        object.__setattr__(self, "bound", bound)

    def __setattr__(self, name, value):
        raise AttributeError("SharedInputBind is immutable")

    @property
    def program(self) -> CIMProgram:
        """The backing fused CIMProgram."""
        return self.shared.program

    def serve(self, x: jnp.ndarray, key: Optional[jax.Array] = None,
              noise: Optional[NoiseConfig] = None, *,
              segments: Optional[jnp.ndarray] = None,
              noise_ids: Optional[jnp.ndarray] = None,
              reference: bool = False,
              point: str = "") -> Dict[str, jnp.ndarray]:
        """One bucketed dispatch of the shared input; the result splits
        along the output axis into {head name: (..., n_i)}.  Isolation
        arguments (`segments`/`noise_ids`) and the operating-point tag
        (`point`) pass through unchanged — a fused-head serve isolates
        rows exactly like any other program."""
        y = self.bound.serve(x, key, noise, segments=segments,
                             noise_ids=noise_ids, reference=reference,
                             point=point)
        return {name: y[..., s:e]
                for (name, _), (s, e) in zip(self.shared.heads,
                                             self.shared._offsets)}

    __call__ = serve

    def stats(self) -> Dict[str, int]:
        """The backing program's compile/bucket counters."""
        return self.shared.program.stats()


# ---------------------------------------------------------------------------
# the global program cache
# ---------------------------------------------------------------------------

_PROGRAM_CACHE: "collections.OrderedDict[tuple, CIMProgram]" = \
    collections.OrderedDict()
_PLAN_PROGRAMS: "collections.OrderedDict[tuple, CIMProgram]" = \
    collections.OrderedDict()
_CACHE_STATS = {"programs_built": 0, "lookups": 0, "hits": 0,
                "evictions": 0}


def _env_capacity() -> int:
    try:
        cap = int(os.environ.get("REPRO_PROGRAM_CACHE_CAP", "512"))
    except ValueError:
        cap = 512
    return max(cap, 1)


# LRU bound on BOTH module-level caches (the precision ladder times model
# churn would otherwise grow them without limit); mutable holder so tests
# can shrink it without monkeypatching the module global
_CACHE_CAPACITY = [_env_capacity()]


def set_program_cache_capacity(capacity: int) -> int:
    """Set the program-cache LRU capacity (entries per cache table) and
    return the previous value.  Shrinking evicts least-recently-used
    entries immediately; evicted programs keep working wherever they are
    already held — eviction only means an equal future compile_program
    call re-plans.  The startup default is $REPRO_PROGRAM_CACHE_CAP
    (512)."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    old = _CACHE_CAPACITY[0]
    _CACHE_CAPACITY[0] = int(capacity)
    for cache in (_PROGRAM_CACHE, _PLAN_PROGRAMS):
        _trim_cache(cache)
    return old


def _trim_cache(cache) -> None:
    while len(cache) > _CACHE_CAPACITY[0]:
        cache.popitem(last=False)
        _CACHE_STATS["evictions"] += 1


def _cache_get(cache, key):
    prog = cache.get(key)
    if prog is not None:
        cache.move_to_end(key)
    return prog


def _cache_put(cache, key, prog) -> None:
    cache[key] = prog
    cache.move_to_end(key)
    _trim_cache(cache)


def _canonical_epilogues(n_layers: int,
                         activations: Optional[Sequence[str]],
                         pools: Optional[Sequence[int]]
                         ) -> Tuple[Tuple[str, ...], Tuple[int, ...]]:
    """plan_network's defaulting, applied eagerly so cache keys are
    canonical (None and the equivalent explicit lists hit one entry)."""
    acts = (("relu",) * (n_layers - 1) + ("none",)
            if activations is None else tuple(activations))
    pls = (1,) * n_layers if pools is None else tuple(pools)
    return acts, pls


def compile_program(specs: Sequence[mapping.LayerSpec],
                    cfg: rt.EngineConfig = rt.EngineConfig(), *,
                    activations: Optional[Sequence[str]] = None,
                    pools: Optional[Sequence[int]] = None,
                    buckets: BatchBuckets = DEFAULT_BUCKETS,
                    verify: str = "off", tune: str = "off",
                    tune_cache: Optional[str] = None) -> CIMProgram:
    """Compile (or fetch from the global cache) the program for a network.

    The cache key is (specs, cfg, activations, pools, buckets) — all
    hashable plan inputs — plus, when tuning, (tune mode, resolved cache
    path) — so every caller of an equal network shares one NetworkPlan
    (planned once; engine.PLAN_COUNT counts) and one executable cache.
    This is the single entry point the model-facing layers (cim_layers
    engine mode, models/cnn, launch/serve) go through.

    Args:
      specs: the network's (conv-tagged) LayerSpecs, in order.
      cfg: shared EngineConfig (noise, sharding, macro, block sizes).
      activations/pools: per-layer epilogues (plan_network defaults).
      buckets: the serve-path batch-bucket ladder.
      verify: cimcheck static verification of the fresh program —
        "strict" raises `repro.analysis.CimcheckError` on any ERROR
        finding, "warn" prints findings to stderr, "off" (default) skips.
        Cache hits skip verification (the program was already checked or
        deliberately not).
      tune: schedule autotuning — "off" (default) plans with the
        EngineConfig heuristics; "analytic" searches block sizes and
        shard kinds with the repro.tuner roofline model; "measure"
        additionally wall-clock times the analytic top-k.  Tuning is
        numerics-neutral: the tuned program's outputs are bit-identical
        to tune="off" (tests/test_tuner.py fuzzes this), and a layer
        whose search keeps the heuristic produces the *same* plan object
        (hash-equal), sharing its executables.
      tune_cache: autotune cache file; None uses
        repro.tuner.default_cache_path(), "" disables persistence for
        this compile.  Corrupt/stale caches degrade to heuristic
        schedules with a TuneCacheWarning — never an error.
    Returns:
      The cached (or freshly planned) CIMProgram.
    """
    if tune not in ("off", "analytic", "measure"):
        raise ValueError(
            f'tune must be "off", "analytic" or "measure", got {tune!r}')
    specs = tuple(specs)
    acts, pls = _canonical_epilogues(len(specs), activations, pools)
    key = (specs, cfg, acts, pls, buckets)
    if tune != "off":
        from repro import tuner
        resolved = (tuner.default_cache_path() if tune_cache is None
                    else tune_cache)
        key = key + (tune, resolved)
    _CACHE_STATS["lookups"] += 1
    prog = _cache_get(_PROGRAM_CACHE, key)
    if prog is not None:
        _CACHE_STATS["hits"] += 1
        return prog
    if tune != "off":
        plan, _ = tuner.tune_network(specs, cfg, acts, pls, mode=tune,
                                     cache_path=resolved)
    else:
        plan = rt.plan_network(specs, cfg, acts, pls)
    prog = _cache_get(_PLAN_PROGRAMS, (plan, buckets))
    if prog is None:
        prog = CIMProgram(plan, buckets)
        _cache_put(_PLAN_PROGRAMS, (plan, buckets), prog)
        _CACHE_STATS["programs_built"] += 1
    _cache_put(_PROGRAM_CACHE, key, prog)
    if verify != "off":
        # inline verification lints the serving graphs (the trace is
        # reused by jit warmup); the exhaustive variant sweep is
        # scripts/cimcheck.py's job
        from repro.analysis import verify_program
        verify_program(prog, mode=verify, graphs="serving")
    return prog


def program_for_plan(plan: rt.NetworkPlan,
                     buckets: BatchBuckets = DEFAULT_BUCKETS) -> CIMProgram:
    """The cached program behind an already-built NetworkPlan (what the
    legacy run_network/run_network_reference entry points dispatch
    through); creates and caches one on first sight of the plan."""
    key = (plan, buckets)
    prog = _cache_get(_PLAN_PROGRAMS, key)
    if prog is None:
        prog = CIMProgram(plan, buckets)
        _cache_put(_PLAN_PROGRAMS, key, prog)
        _CACHE_STATS["programs_built"] += 1
    return prog


def program_cache_stats() -> Dict[str, int]:
    """Global program-cache counters: programs (live cached programs),
    programs_built, lookups, hits (compile_program key hits), evictions
    (LRU drops across both cache tables) and capacity (the LRU bound —
    set_program_cache_capacity / $REPRO_PROGRAM_CACHE_CAP)."""
    return dict(_CACHE_STATS, programs=len(_PLAN_PROGRAMS),
                capacity=_CACHE_CAPACITY[0])


def clear_program_cache() -> None:
    """Drop every cached program and reset the cache counters (tests /
    long-lived processes re-keying on fresh configs)."""
    _PROGRAM_CACHE.clear()
    _PLAN_PROGRAMS.clear()
    for k in list(_CACHE_STATS):
        _CACHE_STATS[k] = 0
