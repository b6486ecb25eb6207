"""Training launcher.

CPU/host-scale entry point used by the examples and integration tests; on a
real cluster the same code runs under the production mesh (the dry-run
proves the sharding).  Supports CIM execution modes, checkpoint/restart via
the fault-tolerant driver, and gradient compression.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 20 --cim-mode fakequant
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.cim_layers import CIMConfig
from repro.core.noise_model import NoiseConfig
from repro.data.lm_data import LMDataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import init_train_state, make_train_step
from repro.optim import AdamWConfig
from repro.runtime.fault_tolerance import FTConfig, TrainDriver


def build(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    noise = NoiseConfig() if args.cim_noise else NoiseConfig(enabled=False)
    cfg = cfg.replace(cim=CIMConfig(mode=args.cim_mode, noise=noise,
                                    max_gamma=2.0**16))
    data = SyntheticLM(LMDataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch))

    def batch_fn(step: int):
        toks, labels = data.batch_at(step)
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(labels)}

    step_fn = jax.jit(make_train_step(
        cfg, AdamWConfig(lr=args.lr), total_steps=args.steps,
        warmup=min(20, args.steps // 10 + 1),
        compress_grads=args.compress_grads), donate_argnums=(0,))
    state = init_train_state(cfg, jax.random.PRNGKey(args.seed),
                             compress_grads=args.compress_grads)
    return cfg, state, step_fn, batch_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced per-arch config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cim-mode", default="bypass",
                    choices=["bypass", "fakequant"])
    ap.add_argument("--cim-noise", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    print(f"compile cache: {enable_compile_cache()}")
    cfg, state, step_fn, batch_fn = build(args)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M cim={cfg.cim.mode}")

    if args.ckpt_dir:
        driver = TrainDriver(
            FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
            step_fn, batch_fn, state_template=state)
        state, history = driver.run(state, args.steps)
        print(f"final loss={history[-1].loss:.4f} "
              f"(restarts={driver.restarts})")
    else:
        t0 = time.time()
        for step in range(args.steps):
            state, metrics = step_fn(state, batch_fn(step))
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"({time.time()-t0:.1f}s)")


if __name__ == "__main__":
    main()
