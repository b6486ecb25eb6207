"""Serve a decoder LM configuration the way `launch/serve.py`'s batched
path does: closed-loop static batches, each a fresh `tf.init_cache`, one
jitted `tf.forward` prefill that yields the first token, then greedy
decode through `jax.jit(make_serve_step(cfg), donate_argnums=(1,))` until
the batch's longest budget is met.  Every step ends in fetching the new
tokens to the host, as a streaming server would.

Spans: `bench.prepare` (cache and prompts), `bench.prefill` and
`bench.prefill.fetch`, `bench.decode` and `bench.decode.fetch`.
"""
from __future__ import annotations

import time

import numpy as np

import cim
import harness
import traffic as tr

# served tokens the check compares, at least (whole batches are compared:
# the program quantizes activations over the whole batch of a call)
CHECK_TOKENS = 256
HEAD_BLOCK = 2048          # reference logits rows per block


class Server:
    STEP = "decode"         # the unit `cim_calls_per_step` counts over

    def __init__(self, cfg: dict, model, traffic: dict, rec):
        self.cfg, self.model, self.traffic, self.rec = cfg, model, traffic, rec
        self.point = model.point(cfg)
        self.b, self.p = traffic["batch"], traffic["prompt_len"]
        self.g_max = int(tr.budgets(traffic).max())
        self.max_len = self.p + self.g_max
        self.prefill_work = self._work(self.b * self.p, self.b * self.p
                                       * (self.p + 1) // 2, self.b,
                                       self.b * self.p)

    def _work(self, tokens, context, head_rows, rows):
        """Least work of one call: model operations of the useful tokens,
        CIM operations and bytes of every row the call computes."""
        ops = bts = 0
        for m, k, n in self.model.gemms(self.cfg, rows):
            o, b = cim.gemm_work(m, k, n, self.point)
            ops, bts = ops + o, bts + b
        return {"model_ops": self.model.model_ops(self.cfg, tokens, context,
                                                  head_rows),
                "cim_ops": ops, "cim_bytes": bts}

    def build(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.configs.base import ModelConfig
        from repro.core.cim_layers import CIMConfig
        from repro.launch.steps import make_serve_step
        from repro.models import transformer as tf

        r_in, r_w, r_out = self.point
        mcfg = ModelConfig(
            name=self.cfg["name"], **self.cfg["model"],
            dtype=self.cfg["dtype"],
            cim=CIMConfig(mode="engine", r_in=r_in, r_w=r_w, r_out=r_out,
                          max_gamma=self.cfg["max_gamma"]))
        kv = jnp.dtype(self.cfg["kv_dtype"])
        self.new_cache = jax.jit(lambda: tf.init_cache(
            mcfg, self.b, max_len=self.max_len, dtype=kv))

        def prefill(params, prompt, cache):
            logits, cache, _ = tf.forward(mcfg, params, prompt, cache=cache)
            tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            return tok, cache

        self.prefill = jax.jit(prefill, donate_argnums=(2,))
        self.step = jax.jit(make_serve_step(mcfg), donate_argnums=(1,))

    def load(self, seed: int) -> None:
        """Weights and prompts of one seed; warms every shape."""
        self.seed = seed
        self.params = self.model.make_params(self.cfg, seed)
        self.prompts = tr.prompt_maker(self.traffic, seed,
                                       self.cfg["model"]["vocab_size"])
        tok, cache = self.prefill(self.params, self.prompts(0),
                                  self.new_cache())
        tok, cache = self.step(self.params, cache, tok)
        np.asarray(tok)
        del cache
        self.batches = []

    def run(self, deadline: float) -> None:
        """Whole static batches until the deadline: none starts after it,
        the one in flight decodes to its end."""
        rec, i = self.rec, 0
        while time.perf_counter() < deadline:
            budgets = tr.batch_budgets(self.traffic, self.seed, i)
            due = time.perf_counter()
            with rec.span("prepare"):
                cache, prompt = self.new_cache(), self.prompts(i)
            with rec.span("prefill", self.prefill_work):
                tok, cache = self.prefill(self.params, prompt, cache)
            with rec.span("prefill.fetch"):
                toks = [np.asarray(tok)]
            times = [time.perf_counter()]
            rec.boundary()
            for j in range(1, self.g_max):
                useful = int(np.sum(budgets > j))
                pos = self.p + j - 1         # position of the fed token
                work = self._work(useful, useful * (pos + 1), useful, self.b)
                with rec.span("decode", work):
                    tok, cache = self.step(self.params, cache, tok)
                with rec.span("decode.fetch"):
                    toks.append(np.asarray(tok))
                times.append(time.perf_counter())
                rec.boundary()
            del cache
            self.batches.append({"index": i, "due": due, "budgets": budgets,
                                 "times": np.asarray(times),
                                 "tokens": np.concatenate(toks, axis=1)})
            i += 1

    def results(self, window: tuple) -> dict:
        """Rates over the window, which holds whole batches only."""
        lo, hi = window
        useful, gaps, ttft = 0, [], []
        for bt in self.batches:
            t, bud = bt["times"], bt["budgets"]
            mask = np.arange(len(t))[None, :] < bud[:, None]     # (B, n)
            useful += int(mask.sum())
            gap_mask = mask[:, 1:]
            gaps.extend(np.broadcast_to(np.diff(t) * 1e3,
                                        gap_mask.shape)[gap_mask])
            ttft.extend([(t[0] - bt["due"]) * 1e3] * self.b)
        metrics = {"tokens_per_s": useful / (hi - lo),
                   "ttft_p95_ms": harness.percentile(ttft, 95)}
        if gaps:
            metrics["itl_p95_ms"] = harness.percentile(gaps, 95)
        return {"metrics": metrics, "attempted": len(ttft), "failed": 0,
                "samples": {"batches": len(self.batches),
                            "tokens": useful, "itl_gaps": len(gaps),
                            "first_tokens": len(ttft)}}

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        import jax
        jax.clear_caches()

    def check(self, control: bool = False) -> dict:
        """Compare the served tokens of whole batches, drawn from the seed
        (the longest first), with the plain reference run over each prompt
        and its served tokens: how far each served token's reference logit
        lies below the reference's best.  Returns the widest such gap, the
        mean gap and the share of served tokens that are not the
        reference's first choice.

        `control` also reads the same numbers of the tokens that the
        reference computed in bfloat16 puts first, at the same positions
        (prefixed `control_`), of the served tokens with the last token of
        the first request altered (prefixed `fault_`), and with every token
        of the first request altered (prefixed `fault_request_`)."""
        import jax.numpy as jnp
        rng = np.random.default_rng([self.seed, 12])
        order = sorted(range(len(self.batches)), key=lambda k: (
            -self.batches[k]["tokens"].shape[1], rng.random()))
        vocab = self.cfg["model"]["vocab_size"]
        got, low, fault, fault_req, compared = [], [], [], [], 0
        for k in order:
            if compared >= CHECK_TOKENS:
                break
            bt = self.batches[k]
            served = bt["tokens"]
            prompt = np.asarray(self.prompts(bt["index"]))
            fed = jnp.asarray(np.concatenate([prompt, served[:, :-1]], 1))
            ref = self.model.hidden(self.cfg, self.params, fed, self.p)
            got.append(_gaps(self.model, self.cfg, self.params, ref, served))
            if control:
                low.append(_gaps(self.model, self.cfg, self.params, ref,
                                 self.model.hidden(self.cfg, self.params, fed,
                                                   self.p,
                                                   dtype=jnp.bfloat16)))
                # one token, and one whole request, altered where produced
                bad = served.copy()
                bad[0, -1] = (bad[0, -1] + 1) % vocab
                fault.append(_gaps(self.model, self.cfg, self.params, ref,
                                   bad))
                bad[0] = (served[0] + 1) % vocab
                fault_req.append(_gaps(self.model, self.cfg, self.params,
                                       ref, bad))
            compared += served.size
        out = dict(_stats(got), compared=compared)
        if control:
            out.update({f"control_{k}": v for k, v in _stats(low).items()})
            out.update({f"fault_{k}": v for k, v in _stats(fault).items()})
            out.update({f"fault_request_{k}": v
                        for k, v in _stats(fault_req).items()})
        return out


def _stats(gaps: list) -> dict:
    """Over (B, n) arrays of gaps, one per compared batch: the widest gap,
    the mean gap, the widest of the requests' mean gaps, and the share of
    tokens that are not the reference's first choice."""
    flat = np.concatenate([g.ravel() for g in gaps])
    return {"logit_gap": float(flat.max()),
            "logit_gap_mean": float(flat.mean()),
            "request_gap": float(max(g.mean(axis=1).max() for g in gaps)),
            "mismatch_share": float(np.mean(flat > 0))}


def _gaps(model, cfg, params, ref_h, picked) -> np.ndarray:
    """(B, n): per position, how far the reference logit of the picked token lies
    below the reference's best.  `picked` is the (B, n) served tokens, or
    low-precision hidden states (B, n, d) whose logits' first choices are
    the picks."""
    import jax.numpy as jnp
    b, n, d = ref_h.shape
    rh = ref_h.reshape(b * n, d)
    out = []
    for s in range(0, b * n, HEAD_BLOCK):
        ref = model.logits(cfg, params, rh[s:s + HEAD_BLOCK])
        if picked.ndim == 2:
            tok = jnp.asarray(picked.reshape(-1)[s:s + HEAD_BLOCK])
        else:
            low = picked.reshape(b * n, d)[s:s + HEAD_BLOCK]
            tok = jnp.argmax(model.logits(cfg, params, low), axis=-1)
        pick = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
        out.append(np.asarray(jnp.max(ref, axis=-1) - pick))
    return np.concatenate(out).reshape(b, n)
