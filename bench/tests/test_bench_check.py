"""The check that decides `correct`, driven through a whole run at a size
the CPU holds, with the timed path sound, replaced by the control (the
plain reference in bfloat16), or broken underneath: `correct` must come
out true only for the sound path.

LeNet runs at its own widths (a smaller batch) and is held to its cell's
limits.  The LM runs at a toy width, where the cell's limits say nothing;
it is held to limits read at that size on the CPU: the mean gap and the
widest mean gap of a request, which the program reads as 0 and the
control as 0.021 and 0.12 or more over three seeds."""
import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import harness  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 4242


def _cell(name):
    cell, entry = harness.find_cell(harness.load_spec(), name)
    cfg, path = harness.config_files(entry)
    return cfg, harness.load_module(path), harness.load_module(
        harness.server_path(cfg["server"]))


TOY_LM_LIMITS = {"logit_gap_mean": {"limit": 0.01},
                 "request_gap": {"limit": 0.05}}


def _correct(limits, cfg, model, trfc, server_module):
    import jax
    out = run.measure(cfg, model, trfc, SEED, 0.3, False, jax.devices(),
                      server_module=server_module)
    ok, _ = run.verdict(out["checks"], limits)
    return ok


class _Served:
    """Stands in for a bound program: `fn(bound, x)` serves."""

    def __init__(self, bound, fn):
        self.bound, self.fn = bound, fn

    def serve(self, x):
        return self.fn(self.bound, x)


def _lenet_fault(kind, model, cfg):
    import jax.numpy as jnp

    def altered(bound, x):                  # image 0 gets image 1's answer
        y = bound.serve(x)
        return y.at[0].set(y[1])

    def half(bound, x):                     # half the batch left out
        y = bound.serve(x[:x.shape[0] // 2])
        return jnp.concatenate([y, y])

    def control(bound, x):                  # the reference in bfloat16
        return model.reference(cfg, model.point(cfg, "r8w4"),
                               control.params, x, dtype=jnp.bfloat16
                               ).astype(jnp.float32)
    return {"answer_altered": altered, "half_batch": half,
            "control": control}.get(kind)


@pytest.mark.parametrize("kind,want", [
    ("sound", True), ("control", False), ("answer_altered", False),
    ("half_batch", False)])
def test_lenet_check(kind, want):
    cfg, model, servers = _cell("lenet5.b10000.r8w4")
    fn = _lenet_fault(kind, model, cfg)

    class Server(servers.Server):
        def load(self, seed):
            super().load(seed)
            if fn is not None:
                fn.params = self.params
                self.bound = _Served(self.bound, fn)

    trfc = {"kind": "image_pool", "batch": 8, "pool": 2, "point": "r8w4"}
    got = _correct(run.limits_for("lenet5.b10000.r8w4"), cfg, model, trfc,
                   type("Servers", (), {"Server": Server}))
    assert got is want


def _lm_step_fault(kind, real):
    """A make_serve_step whose step is broken as `kind` says."""
    def make(cfg, **kw):
        step = real(cfg, **kw)

        def broken(params, cache, tok):
            nxt, new = step(params, cache, tok)
            if kind == "token_altered":         # one token changed
                return nxt.at[0, 0].set((nxt[0, 0] + 1) % cfg.vocab_size), new
            if kind == "request_altered":       # one request's every token
                return nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size), new
            if kind == "state_unchanged":       # the cache never advances
                return nxt, cache
            b = nxt.shape[0] // 2               # half the batch left out
            return nxt.at[b:].set(nxt[:b]), new
        return broken
    return make


@pytest.mark.parametrize("kind,want", [
    ("sound", True), ("control", False), ("token_altered", False),
    ("request_altered", False), ("state_unchanged", False),
    ("half_batch", False)])
def test_lm_check(kind, want, monkeypatch):
    from repro.launch import steps
    cfg, model, servers = _cell("olmo1b.decode.b64")
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        d_ff=128, vocab_size=512)
    trfc = {"kind": "static_batches", "batch": 4, "prompt_len": 8,
            "output": {"dist": "fixed", "tokens": 6}}
    if kind not in ("sound", "control"):
        monkeypatch.setattr(steps, "make_serve_step",
                            _lm_step_fault(kind, steps.make_serve_step))

    class Server(servers.Server):
        def check(self, control=False):
            if kind != "control":
                return super().check()
            # the control in the program's place: the gaps of the tokens
            # the bfloat16 reference puts first
            got = super().check(control=True)
            return {"logit_gap_mean": got["control_logit_gap_mean"],
                    "request_gap": got["control_request_gap"],
                    "compared": got["compared"]}

    got = _correct(TOY_LM_LIMITS, cfg, model, trfc,
                   type("Servers", (), {"Server": Server}))
    assert got is want
