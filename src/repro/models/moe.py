"""Mixture-of-experts FFN with shard_map expert execution.

Parallelism (DESIGN.md §5): tokens are data-parallel over ("pod","data"),
every expert's FFN is tensor-parallel over "model" (Megatron split on d_ff).
Inside the shard_map body everything is *local*: top-k routing results are
sorted per shard, tokens are gathered into fixed-capacity expert groups
(dropped-token discipline, capacity_factor), the grouped GEMMs run as
batched einsums over the expert axis, and the down-projection partials are
psum'd over "model".

Per-expert ABN: the CIM fakequant path quantizes each expert's weights with
per-(expert, channel) scales and applies per-expert gamma/beta — the paper's
distribution-aware reshaping argument is strongest exactly here, since every
expert sees a different token distribution.

CIM modes: "fakequant" runs the batched-einsum reference with *per-expert*
activation statistics (segment quantization over the expert axis) and the
zero-point folded inside the ADC floor; "engine" routes every expert's
capacity-grouped GEMM through one compiled CIM program per (fan_in, fan_out,
precision) shape — the experts are the plan-once/serve-many case (same
LayerSpec, E different binds), so E experts hit a single program-cache
entry.  The two paths are bit-exact in clean mode.  Unknown modes raise
ValueError — an engine-mode serving config can never silently fall back to
an unquantized float einsum.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P, get_abstract_mesh

from repro.core import abn as abn_lib
from repro.core import mapping
from repro.core import noise_model as nm
from repro.core.cim_layers import (CIMConfig, _code_gain,
                                   cim_grouped_dense_apply)
from repro.core.quantization import (adc_quantize, quantize_act,
                                     quantize_weight, rounding_barrier)
from repro.models.common import activation_fn
from repro.models.sharding import BATCH, TP, mesh_spec, shard


def init_moe(key: jax.Array, d: int, f: int, n_experts: int,
             cim: Optional[CIMConfig] = None) -> Dict:
    """Router + expert bank params: w_gate/w_up (E, D, F), w_down (E, F, D),
    per-expert ABN gamma/beta on the down-projection's D outputs."""
    ks = jax.random.split(key, 4)
    s_in = (1.0 / d) ** 0.5
    s_out = (1.0 / f) ** 0.5
    return {
        "router": s_in * jax.random.normal(ks[0], (d, n_experts), jnp.float32),
        "w_gate": s_in * jax.random.normal(ks[1], (n_experts, d, f), jnp.float32),
        "w_up": s_in * jax.random.normal(ks[2], (n_experts, d, f), jnp.float32),
        "w_down": s_out * jax.random.normal(ks[3], (n_experts, f, d), jnp.float32),
        "abn_log_gamma": jnp.zeros((n_experts, d), jnp.float32),
        "abn_beta": jnp.zeros((n_experts, d), jnp.float32),
    }


def _get_expert_w(params: Dict, name: str, dtype) -> jnp.ndarray:
    """Raw or deploy-quantized expert bank; int8 dequant fuses on TPU."""
    if f"{name}_q" in params:
        return (params[f"{name}_q"].astype(dtype)
                * params[f"{name}_scale"][..., None, :].astype(dtype))
    return params[name]


def _expert_abn(abn: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
                e: int, f: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-expert ABN params, defaulting to log2(gamma)=4 / beta=0 for the
    projections that carry no learned reshaping (gate/up)."""
    if abn is not None:
        return abn[0], abn[1]
    return (jnp.full((e, f), 4.0, jnp.float32),
            jnp.zeros((e, f), jnp.float32))


def _expert_gemm_engine(x_g: jnp.ndarray, w: jnp.ndarray, cim: CIMConfig,
                        abn: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
                        key: Optional[jax.Array],
                        reference: bool) -> jnp.ndarray:
    """(E, C, D) x (E, D, F) in ONE grouped CIM call a row tile.

    Each expert's C capacity rows are one group: its own weights and ABN,
    its own activation range over its C rows and, under noise, the draws
    of fold_in(key, e) — the bits E separate one-layer programs would give
    (cim_layers.cim_grouped_dense_apply)."""
    e, c, d = x_g.shape
    f = w.shape[2]
    # entry/exit barriers: match _expert_gemm's fakequant branch so the
    # digital glue around the expert GEMMs (activation, gating, scatter)
    # is the same isolated subgraph in both modes (rounding_barrier)
    x_g = rounding_barrier(x_g)
    lg, bt = _expert_abn(abn, e, f)
    p = {"w": w.astype(jnp.float32), "abn_log_gamma": lg, "abn_beta": bt}
    y = cim_grouped_dense_apply(p, x_g.astype(jnp.float32), cim, key=key,
                                reference=reference)
    return rounding_barrier(y).astype(x_g.dtype)


def _expert_gemm(x_g: jnp.ndarray, w: jnp.ndarray, cim: CIMConfig,
                 abn: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                 *, key: Optional[jax.Array] = None,
                 reference: bool = False) -> jnp.ndarray:
    """(E, C, D) x (E, D, F) -> (E, C, F) through the configured CIM path.

    fakequant: per-expert activation statistics (segment quantization over
    the expert axis), per-(expert, channel) weight scales, per-expert ABN,
    and the zero-point folded into the ABN offset *inside* the per-row-tile
    ADC floor — the same arithmetic as core.cim_layers._fakequant_forward,
    so it is bit-exact with mode="engine" in clean mode.  engine: compiled
    per-expert programs (_expert_gemm_engine).  bypass/deploy: plain
    einsum.  Anything else raises ValueError."""
    if cim.mode in ("bypass", "deploy"):
        return jnp.einsum("ecd,edf->ecf", x_g, w.astype(x_g.dtype))
    if cim.mode == "engine":
        return _expert_gemm_engine(x_g, w, cim, abn, key, reference)
    if cim.mode != "fakequant":
        raise ValueError(
            f"moe expert GEMM does not support CIM mode {cim.mode!r}; "
            "use fakequant, engine, bypass or deploy")
    e, _, _ = x_g.shape
    fan_in, fan_out = w.shape[1], w.shape[2]
    # entry barrier mirroring _expert_gemm_engine (rounding_barrier)
    x_g = rounding_barrier(x_g)
    aq = quantize_act(x_g.astype(jnp.float32), cim.r_in,
                      segment_ids=jnp.arange(e, dtype=jnp.int32),
                      num_segments=e)                 # per-expert stats
    wq = quantize_weight(w, cim.r_w, axis=1)          # scale (E, 1, F)
    lg, bt = _expert_abn(abn, e, fan_out)
    gamma = abn_lib.abn_gamma(
        abn_lib.ABNParams(lg, bt), gamma_bits=cim.gamma_bits,
        max_gamma=cim.max_gamma)[:, None, :]          # (E, 1, F)
    beta = bt[:, None, :]
    g0 = _code_gain(cim, fan_in)
    mid = 2.0 ** (cim.r_out - 1)

    if cim.noise.enabled and key is not None:
        key, k2 = jax.random.split(key)
        res_v = jax.vmap(
            lambda kk: nm.sample_column_residues(kk, fan_out, cim.r_w,
                                                 cim.noise, cim.macro)
        )(jax.random.split(k2, e))                    # (E, F) per expert
        lsb_v = cim.macro.alpha_adc() * cim.macro.vddh \
            / 2.0 ** (cim.r_out - 1)
        offset_codes = gamma * res_v[:, None, :] / lsb_v
    else:
        offset_codes = 0.0

    # K > n_rows splits into row tiles with per-tile ADC conversions,
    # mirroring _fakequant_forward / the engine schedule exactly.
    row_tiles = -(-fan_in // cim.macro.n_rows)
    # materialized ADC gain (quantization.rounding_barrier): the floor /
    # dequant chain must see the identical float in every fusion context
    gain = rounding_barrier(gamma * g0)
    zp = aq.zero / aq.scale                           # (E, 1, 1)
    dp_hat = jnp.zeros(x_g.shape[:-1] + (fan_out,), jnp.float32)
    for ks, ksz in mapping.split_k_slices(fan_in, row_tiles):
        ke = ks + ksz
        dp = jnp.einsum("ecd,edf->ecf", aq.q[..., ks:ke], wq.q[:, ks:ke, :])
        zp_dp = zp * jnp.sum(wq.q[:, ks:ke, :], axis=1, keepdims=True)
        if cim.noise.enabled and key is not None:
            key, k1 = jax.random.split(key)
            dp = dp + nm.thermal_sigma_dp(cim.noise, cim.r_out, g0) \
                * jax.random.normal(k1, dp.shape)
        beta_eff = (beta + offset_codes) + gain * zp_dp
        code = adc_quantize(dp, r_out=cim.r_out, gain=gain,
                            beta_codes=beta_eff)
        dp_hat = dp_hat + (code - mid - beta) / gain
    return rounding_barrier(dp_hat * aq.scale * wq.scale).astype(x_g.dtype)


def _moe_local(x: jnp.ndarray, probs: jnp.ndarray, top_idx: jnp.ndarray,
               w_gate: jnp.ndarray, w_up: jnp.ndarray, w_down: jnp.ndarray,
               abn_lg: jnp.ndarray, abn_b: jnp.ndarray,
               key: Optional[jax.Array] = None, *,
               n_experts: int, top_k: int, capacity_factor: float,
               cim: CIMConfig, act: str, psum_axis: Optional[str],
               reference: bool = False) -> jnp.ndarray:
    """Local (per data shard) dropped-token expert execution.

    x (t, D); probs/top_idx (t, k).  Returns (t, D)."""
    t, d = x.shape
    cap = int(capacity_factor * top_k * t / n_experts + 0.5)
    cap = max(8, min(cap, t * top_k))

    flat_e = top_idx.reshape(-1)                       # (t*k,)
    flat_tok = jnp.repeat(jnp.arange(t), top_k)
    flat_p = probs.reshape(-1)
    order = jnp.argsort(flat_e)                        # stable
    e_sorted = flat_e[order]
    # rank within the expert group
    same = jax.nn.one_hot(e_sorted, n_experts, dtype=jnp.int32)
    rank = (jnp.cumsum(same, axis=0) - 1)[jnp.arange(t * top_k), e_sorted]
    keep = rank < cap
    slot = e_sorted * cap + rank                       # (t*k,) flat slot id
    slot = jnp.where(keep, slot, n_experts * cap)      # overflow bin

    # scatter token ids / gates into the capacity grid
    tok_grid = jnp.zeros((n_experts * cap + 1,), jnp.int32).at[slot].set(
        flat_tok[order], mode="drop")
    gate_grid = jnp.zeros((n_experts * cap + 1,), flat_p.dtype).at[slot].set(
        jnp.where(keep, flat_p[order], 0.0), mode="drop")
    tok_grid = tok_grid[:-1].reshape(n_experts, cap)
    gate_grid = gate_grid[:-1].reshape(n_experts, cap)

    k_up = k_gate = k_down = None
    if key is not None:
        k_up, k_gate, k_down = (jax.random.fold_in(key, i) for i in range(3))
    x_g = x[tok_grid]                                  # (E, C, D)
    h_up = _expert_gemm(x_g, w_up, cim, key=k_up, reference=reference)
    fn = activation_fn(act)
    if w_gate is not None:
        h = fn(_expert_gemm(x_g, w_gate, cim, key=k_gate,
                            reference=reference)) * h_up
    else:
        h = fn(h_up)
    y_g = _expert_gemm(h, w_down, cim, abn=(abn_lg, abn_b), key=k_down,
                       reference=reference)            # (E, C, D)
    y_g = y_g * gate_grid[..., None].astype(y_g.dtype)

    out = jnp.zeros((t, d), y_g.dtype).at[tok_grid.reshape(-1)].add(
        y_g.reshape(-1, d))
    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    return out


def moe_block(params: Dict, x: jnp.ndarray, *, n_experts: int, top_k: int,
              capacity_factor: float, cim: CIMConfig, act: str = "silu",
              key: Optional[jax.Array] = None, reference: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, D) -> (out (B, S, D), aux load-balance loss scalar).

    `key` seeds the experts' CIM noise model (a distinct fold per
    projection bank and per expert).  `reference` asks the engine path to
    run its interpret-mode oracle instead of the Pallas kernel (noise-key
    parity tests).  mode="engine" always executes the *local* expert path:
    the compiled programs own their sharding (cim.sharding), so the outer
    data/tensor shard_map is skipped rather than nested."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)

    logits = (xf.astype(jnp.float32) @ params["router"])
    probs_full = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs_full, top_k)
    top_p = (top_p / jnp.sum(top_p, -1, keepdims=True)).astype(x.dtype)

    # Switch-style load-balance aux loss (computed globally, cheap)
    me = jnp.mean(probs_full, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_idx[:, 0], n_experts), axis=0)
    aux = n_experts * jnp.sum(me * ce)

    mesh = get_abstract_mesh()
    kwargs = dict(n_experts=n_experts, top_k=top_k,
                  capacity_factor=capacity_factor, cim=cim, act=act,
                  reference=reference)
    w_gate = _get_expert_w(params, "w_gate", x.dtype)
    w_up = _get_expert_w(params, "w_up", x.dtype)
    w_down = _get_expert_w(params, "w_down", x.dtype)
    if mesh.empty or cim.mode == "engine":
        out = _moe_local(xf, top_p, top_idx, w_gate, w_up,
                         w_down, params["abn_log_gamma"],
                         params["abn_beta"], key, psum_axis=None, **kwargs)
    else:
        names = set(mesh.axis_names)
        batch_axes = tuple(a for a in BATCH if a in names)
        n_batch = 1
        for a in batch_axes:
            n_batch *= mesh.shape[a]
        if (b * s) % max(n_batch, 1) != 0:     # e.g. single-token decode
            batch_axes = ()
        tp = TP if TP in names else None
        body = functools.partial(_moe_local, psum_axis=tp, **kwargs)
        tok_spec = P(batch_axes if batch_axes else None, None)
        if key is None:
            def body_nokey(xs, ps, ti, wg, wu, wd, lg, bt):
                return body(xs, ps, ti, wg, wu, wd, lg, bt, None)
            out = shard_map(
                body_nokey, mesh=mesh,
                in_specs=(tok_spec, tok_spec, tok_spec,
                          P(None, None, tp), P(None, None, tp),
                          P(None, tp, None), P(None, None), P(None, None)),
                out_specs=tok_spec,
            )(xf, top_p, top_idx, w_gate, w_up,
              w_down, params["abn_log_gamma"], params["abn_beta"])
        else:
            out = shard_map(
                body, mesh=mesh,
                in_specs=(tok_spec, tok_spec, tok_spec,
                          P(None, None, tp), P(None, None, tp),
                          P(None, tp, None), P(None, None), P(None, None),
                          P(None)),
                out_specs=tok_spec,
            )(xf, top_p, top_idx, w_gate, w_up,
              w_down, params["abn_log_gamma"], params["abn_beta"], key)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# a dropless layer told which experts it holds (expert parallelism's share)
# ---------------------------------------------------------------------------

def init_moe_held(key: jax.Array, d: int, f: int, n_experts: int,
                  held: int, shared_f: int = 0,
                  cim: Optional[CIMConfig] = None) -> Dict:
    """Router over all `n_experts` (D, E), the `held` experts' gate/up
    (held, D, F) and down (held, F, D) banks as grouped CIM linears, and
    the shared experts as one gated MLP of width `shared_f` (0: none)."""
    from repro.core.cim_layers import init_cim_grouped
    from repro.models.common import init_mlp
    ks = jax.random.split(key, 5)
    p = {"router": (1.0 / d) ** 0.5 * jax.random.normal(
            ks[0], (d, n_experts), jnp.float32),
         "w_gate": init_cim_grouped(ks[1], held, d, f, cim),
         "w_up": init_cim_grouped(ks[2], held, d, f, cim),
         "w_down": init_cim_grouped(ks[3], held, f, d, cim)}
    if shared_f:
        p["shared"] = init_mlp(ks[4], d, shared_f, True, cim)
    return p


def route_topk(x: jnp.ndarray, router: jnp.ndarray, top_k: int, *,
               norm_topk: bool, scale: float
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy softmax routing, digital: x (t, D) . router in float32 at
    HIGHEST precision, softmax over every expert, the top k; weights
    renormalised over the k when `norm_topk`, then times `scale`.
    Returns (weights (t, k) float32, expert ids (t, k) int32)."""
    logits = jnp.dot(x.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_p * scale, top_idx


def held_layout(top_idx: jnp.ndarray, top_p: jnp.ndarray, *, held: int,
                share: int, bm: int
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The grouped row layout of the routes to the held experts.

    Every route (token, expert) with expert in [share*held,
    (share+1)*held) gets one row, grouped by held expert in expert order,
    each group from a multiple of `bm`.  The buffer holds every route
    that can reach the held experts (each token routes to an expert at
    most once), so no route is ever dropped.  Returns (row_group (M,),
    row_token (M,), row_weight (M,)): a padding row has group `held`,
    token 0 and weight 0."""
    t, k = top_idx.shape
    local = top_idx.reshape(-1) - share * held
    mine = (local >= 0) & (local < held)
    grp = jnp.where(mine, local, held).astype(jnp.int32)
    wgt = jnp.where(mine, top_p.reshape(-1), 0.0)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    most = min(t * k, held * t)
    rows = -(-(most + held * (bm - 1)) // bm) * bm
    counts = jnp.zeros((held + 1,), jnp.int32).at[grp].add(1)
    blocks = (counts[:held] + bm - 1) // bm
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(blocks) * bm])     # (held + 1,)
    first = jnp.cumsum(counts) - counts                    # sorted offsets
    order = jnp.argsort(grp, stable=True)
    g_sorted = grp[order]
    rank = jnp.arange(t * k, dtype=jnp.int32) - first[g_sorted]
    dest = jnp.where(g_sorted < held, start[g_sorted] + rank, rows)
    row_group = jnp.full((rows,), held, jnp.int32).at[dest].set(
        g_sorted, mode="drop")
    row_token = jnp.zeros((rows,), jnp.int32).at[dest].set(
        tok[order], mode="drop")
    row_weight = jnp.zeros((rows,), jnp.float32).at[dest].set(
        wgt[order], mode="drop")
    return row_group, row_token, row_weight


# layout rows one grouped call serves: a longer layout (a prompt's) runs in
# chunks of this many rows, its activation ranges taken over all of it
CHUNK_ROWS = 16384


def moe_held_block(params: Dict, x: jnp.ndarray, *, n_experts: int,
                   top_k: int, held: int, share: int, norm_topk: bool,
                   routed_scale: float, cim: CIMConfig, act: str = "silu"
                   ) -> jnp.ndarray:
    """x (B, S, D) -> (B, S, D): the part of a fine-grained MoE layer that
    a chip holding experts [share*held, (share+1)*held) computes.

    Routes over all `n_experts` (`route_topk`); routes to other experts
    contribute nothing.  Dropless: every route to a held expert is
    computed.  The routed rows are laid out by held expert
    (`held_layout`), and each of the gate, up and down banks is one
    grouped CIM call whose activation ranges are per expert, over the rows
    routed to it (cim_layers.cim_grouped_apply).  A layout longer than
    CHUNK_ROWS (a multiple of the layout's block) runs in chunks, the
    ranges taken over the whole layout first, so its bits do not depend
    on the chunking.  The shared experts' MLP is added once."""
    from repro.core.cim_layers import cim_grouped_apply, group_block_rows
    from repro.models.common import mlp_block
    from repro.runtime.engine import group_ranges
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    bm = group_block_rows(cim)
    with jax.named_scope("lm.moe_route"):
        top_p, top_idx = route_topk(xf, params["router"], top_k,
                                    norm_topk=norm_topk, scale=routed_scale)
    with jax.named_scope("lm.moe_dispatch"):
        row_group, row_token, row_weight = held_layout(
            top_idx, top_p, held=held, share=share, bm=bm)
        m = row_group.shape[0]
        chunk = min(m, CHUNK_ROWS)
        n_chunks = -(-m // chunk)
        pad = n_chunks * chunk - m
        row_group = jnp.pad(row_group, (0, pad), constant_values=held)
        row_token = jnp.pad(row_token, (0, pad))
        row_weight = jnp.pad(row_weight, (0, pad))
    fn = activation_fn(act)
    ranged = n_chunks > 1 and cim.mode == "engine"

    def ranges(rows_min, rows_max):
        if not ranged:
            return None
        return group_ranges(rows_min, rows_max, row_group, held, cim.r_in)

    r_x = ranges(jnp.min(xf, 1)[row_token], jnp.max(xf, 1)[row_token])

    def hidden(rg, tok):
        with jax.named_scope("lm.moe_dispatch"):
            rows = xf[tok]
        gate = cim_grouped_apply(params["w_gate"], rows, rg, cim, ranges=r_x)
        up = cim_grouped_apply(params["w_up"], rows, rg, cim, ranges=r_x)
        return fn(gate) * up

    def combine(out, rg, tok, w, y):
        with jax.named_scope("lm.moe_combine"):
            dest = jnp.where(rg < held, tok, t)
            return out.at[dest].add(y * w[:, None].astype(y.dtype),
                                    mode="drop")

    out = jnp.zeros((t, d), x.dtype)
    if n_chunks == 1:
        h = hidden(row_group, row_token)
        y = cim_grouped_apply(params["w_down"], h, row_group, cim)
        out = combine(out, row_group, row_token, row_weight, y)
    else:
        split = (row_group.reshape(n_chunks, chunk),
                 row_token.reshape(n_chunks, chunk),
                 row_weight.reshape(n_chunks, chunk))
        h = jax.lax.map(lambda a: hidden(a[0], a[1]), split[:2])
        r_h = ranges(jnp.min(h, 2).reshape(-1), jnp.max(h, 2).reshape(-1))

        def down(acc, a):
            rg, tok, w, hc = a
            y = cim_grouped_apply(params["w_down"], hc, rg, cim, ranges=r_h)
            return combine(acc, rg, tok, w, y), None

        out, _ = jax.lax.scan(down, out, split + (h,))
    out = out.reshape(b, s, d)
    if "shared" in params:
        out = out + mlp_block(params["shared"], x, cim, act)
    return out
