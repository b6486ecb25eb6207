"""The one traffic generator: every mix is a JSON file of parameters under
bench/traffic/, read here, and everything it makes comes from `--seed`.

Kinds of mix:

* `image_pool` — closed-loop batches of `batch` images, cycled from a pool
  of `pool` distinct batches of pseudo-MNIST digits made on the device.
  `point` names the precision operating point the requests ask for, one of
  the configuration's `points`.
* `static_batches` — closed-loop static batches of `batch` requests, each
  with a prompt of `prompt_len` random ids and an output budget.  The
  budgets of every batch are one fixed multiset (the `batch` midpoint
  quantiles of the output distribution), dealt to the rows in an order
  drawn from the seed, so every seed asks for the same work.
"""
from __future__ import annotations

import math
import statistics
from pathlib import Path

import numpy as np

from harness import BENCH, load_json, traffic_path


def load(name: str, bench: Path = BENCH) -> dict:
    return load_json(traffic_path(name, bench))


def key_of(seed: int, *path: int):
    """A JAX key from a seed of any size (PRNGKey keeps only 32 bits)."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


# ---------------------------------------------------------------------------
# image_pool
# ---------------------------------------------------------------------------

# seven-segment strokes per digit: top, tl, tr, mid, bl, br, bot, diagonal
_SEGS = ((1, 1, 1, 0, 1, 1, 1, 0), (0, 0, 1, 0, 0, 1, 0, 0),
         (1, 0, 1, 1, 1, 0, 1, 0), (1, 0, 1, 1, 0, 1, 1, 0),
         (0, 1, 1, 1, 0, 1, 0, 0), (1, 1, 0, 1, 0, 1, 1, 0),
         (1, 1, 0, 1, 1, 1, 1, 0), (1, 0, 1, 0, 0, 1, 0, 1),
         (1, 1, 1, 1, 1, 1, 1, 0), (1, 1, 1, 1, 0, 1, 1, 0))


def image_pool(traffic: dict, seed: int, h: int = 28, w: int = 28):
    """(pool, batch, h, w, 1) float32 pseudo-MNIST digits in [0, 1], made
    on the device in one jitted call: seven-segment strokes of random
    thickness, shifted by up to 3 pixels, plus Gaussian pixel noise."""
    import jax
    import jax.numpy as jnp

    n = traffic["pool"] * traffic["batch"]

    @jax.jit
    def make(key):
        kd, ks, kt, kn = jax.random.split(key, 4)
        segs = jnp.asarray(_SEGS, jnp.float32)[
            jax.random.randint(kd, (n,), 0, 10)]             # (n, 8)
        sh = jax.random.randint(ks, (n, 2, 1, 1), -3, 4)
        th = jax.random.randint(kt, (n, 1, 1), 1, 3)
        y = jnp.arange(h)[None, :, None] - sh[:, 0]
        x = jnp.arange(w)[None, None, :] - sh[:, 1]
        x0, x1, y0, ym, y1 = 7, 20, 5, 14, 23

        def hline(yc):
            return (y >= yc - th) & (y < yc + th) & (x >= x0) & (x < x1)

        def vline(xc, ya, yb):
            return (x >= xc - th) & (x < xc + th) & (y >= ya) & (y < yb)

        xd = x1 - (x1 - x0) * (y - y0) // (y1 - y0)
        strokes = (hline(y0), vline(x0, y0, ym), vline(x1, y0, ym),
                   hline(ym), vline(x0, ym, y1), vline(x1, ym, y1),
                   hline(y1), (y >= y0) & (y < y1) & (x >= xd - th)
                   & (x < xd + th))
        img = jnp.zeros((n, h, w), jnp.float32)
        for i, m in enumerate(strokes):
            img = jnp.maximum(img, segs[:, i, None, None] * m)
        img = img + 0.15 * jax.random.normal(kn, img.shape)
        return jnp.clip(img, 0.0, 1.0).reshape(
            traffic["pool"], traffic["batch"], h, w, 1)

    return make(key_of(seed, 0))


# ---------------------------------------------------------------------------
# static_batches
# ---------------------------------------------------------------------------

def budgets(traffic: dict) -> np.ndarray:
    """The output budgets every batch holds, ascending (int64, >= 1)."""
    out, b = traffic["output"], traffic["batch"]
    if out["dist"] == "fixed":
        return np.full(b, out["tokens"], np.int64)
    if out["dist"] == "lognormal":
        z = statistics.NormalDist()
        vals = [out["median"] * math.exp(out["sigma"]
                                         * z.inv_cdf((i + 0.5) / b))
                for i in range(b)]
        return np.clip(np.rint(vals), 1, out["cap"]).astype(np.int64)
    raise ValueError(f"unknown output distribution {out['dist']!r}")


def batch_budgets(traffic: dict, seed: int, index: int) -> np.ndarray:
    """Budgets of batch `index`, one per row, in the seed's order."""
    rng = np.random.default_rng([seed, index])
    return rng.permutation(budgets(traffic))


def prompt_maker(traffic: dict, seed: int, vocab: int):
    """A jitted `index -> (batch, prompt_len) int32` of random ids."""
    import jax
    import jax.numpy as jnp

    base = key_of(seed, 1)
    shape = (traffic["batch"], traffic["prompt_len"])

    # the key is an argument, not a constant of the program, so one
    # compiled program serves every seed from the persistent cache
    @jax.jit
    def make(key, index):
        return jax.random.randint(jax.random.fold_in(key, index), shape,
                                  0, vocab, jnp.int32)

    return lambda index: make(base, jnp.int32(index))
