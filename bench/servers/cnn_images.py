"""Serve a CNN configuration through the compiled CIM program: the
configuration's layers as one `compile_program(...)` at the cell's batch,
then closed-loop batches of images, each one `bound.serve(x)` whose
outputs are fetched to the host, as a server returns them.

Spans: `bench.batch` (dispatch), `bench.batch.fetch` (wait and copy).
"""
from __future__ import annotations

import time

import numpy as np

import cim
import traffic as tr

# served batches compared with the reference after the window
CHECK_BATCHES = 8


class Server:
    STEP = "batch"          # the unit `cim_calls_per_step` counts over

    def __init__(self, cfg: dict, model, traffic: dict, rec):
        self.cfg, self.model, self.traffic, self.rec = cfg, model, traffic, rec
        self.point = model.point(cfg, traffic["point"])
        self.batch = traffic["batch"]
        ops = 2 * sum(m * k * n for m, k, n in model.gemms(cfg, 1))
        if ops != 2 * cfg["macs_per_image"]:
            raise ValueError(f"layers give {ops // 2} MACs per image, the "
                             f"configuration states {cfg['macs_per_image']}")
        ops = bts = 0
        for m, k, n in model.gemms(cfg, self.batch):
            o, b = cim.gemm_work(m, k, n, self.point)
            ops, bts = ops + o, bts + b
        self.work = {"model_ops": ops, "cim_ops": ops, "cim_bytes": bts}

    def build(self) -> None:
        """The program at this cell's batch and operating point, its one
        bucket the batch itself (as `models/cnn.lenet_program` builds its
        network, with the configuration's layers)."""
        from repro.core.cim_layers import CIMConfig, _engine_config
        from repro.core.mapping import LayerSpec, conv_layer_spec
        from repro.runtime.program import BatchBuckets, compile_program

        r_in, r_w, r_out = self.point
        r = dict(r_in=r_in, r_w=r_w, r_out=r_out)
        specs = []
        for l in self.model.layers(self.cfg, self.batch):
            if l["kind"] == "conv":
                h, w, c = l["hwc"]
                specs.append(conv_layer_spec(
                    self.batch, h, w, c, l["c_out"], kh=l["kh"], kw=l["kw"],
                    padding=l["padding"], **r))
            else:
                specs.append(LayerSpec(m=self.batch, k=l["k"], n=l["n"], **r))
        layers = self.cfg["model"]["layers"]
        cimc = CIMConfig(mode="engine", **r, max_gamma=self.cfg["max_gamma"])
        self.program = compile_program(
            specs, _engine_config(cimc),
            activations=[l["activation"] for l in layers],
            pools=[l["pool"] for l in layers],
            buckets=BatchBuckets(min_bucket=self.batch))
        got = [(l.spec.m, l.spec.k, l.spec.n)
               for l in self.program.plan.layers]
        if got != self.model.gemms(self.cfg, self.batch):
            raise ValueError(f"program layers {got} differ from the "
                             "configuration's")

    def load(self, seed: int) -> None:
        """Weights and the image pool of one seed; warms every shape."""
        self.seed = seed
        self.params = self.model.make_params(self.cfg, self.point, seed)
        self.bound = self.program.bind(self.params)
        # split once here: indexing the pool inside the window would compile
        self.pool = list(tr.image_pool(self.traffic, seed))
        np.asarray(self.bound.serve(self.pool[0]))
        self.served = []          # (pool index, host outputs)

    def run(self, deadline: float) -> None:
        """Whole batches until the deadline."""
        rec, i = self.rec, 0
        while time.perf_counter() < deadline:
            j = i % self.traffic["pool"]
            with rec.span("batch", self.work):
                out = self.bound.serve(self.pool[j])
            with rec.span("batch.fetch"):
                host = np.asarray(out)
            self.served.append((j, host))
            rec.boundary()
            i += 1

    def results(self, window: tuple) -> dict:
        lo, hi = window
        n = len(self.served) * self.batch
        return {"metrics": {"images_per_s": n / (hi - lo)},
                "attempted": n, "failed": 0,
                "samples": {"batches": len(self.served), "images": n}}

    def release(self) -> None:
        """Drop the program's bound state before the reference runs."""
        self.bound = None

    def check(self, control: bool = False) -> dict:
        """Compare a sample of served batches, drawn from the seed, with
        the plain reference of their inputs.  `control` also reads the
        reference computed in bfloat16 against the float32 one."""
        import jax.numpy as jnp
        rng = np.random.default_rng([self.seed, 11])
        picks = rng.choice(len(self.served),
                           min(CHECK_BATCHES, len(self.served)),
                           replace=False)
        refs, got, low = {}, [], []
        for p in sorted(picks):
            j, host = self.served[p]
            if j not in refs:
                refs[j] = np.asarray(self.model.reference(
                    self.cfg, self.point, self.params, self.pool[j]))
            got.append(_gaps(host, refs[j]))
            if control:
                low.append(_gaps(np.asarray(self.model.reference(
                    self.cfg, self.point, self.params, self.pool[j],
                    dtype=jnp.bfloat16), np.float32), refs[j]))
        out = dict(_stats(np.concatenate(got)),
                   compared=len(picks) * self.batch)
        if control:
            out.update({f"control_{k}": v for k, v in
                        _stats(np.concatenate(low)).items()})
        return out


def _gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Each output's gap from the reference, in units of the reference
    batch's largest output magnitude."""
    return (np.abs(got.astype(np.float64) - ref).ravel()
            / max(float(np.max(np.abs(ref))), 1e-30))


def _stats(gaps: np.ndarray) -> dict:
    """The widest gap, and the share of outputs off by more than 1e-6."""
    return {"output_gap": float(gaps.max()),
            "mismatch_share": float(np.mean(gaps > 1e-6))}
