"""lenet5-mnist: weights from the seed, least work from shapes, and the plain
reference of the network (the configuration's conv and dense layers in
order, each conv with its max-pool, every layer through the CIM layer of
bench/cim.py)."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

import cim
from traffic import key_of


def point(cfg: dict, name: str) -> cim.Point:
    p = cfg["points"][name]
    return (p["r_in"], p["r_w"], p["r_out"])


def layers(cfg: dict, batch: int) -> List[dict]:
    """Per layer: its GEMM (m, k, n), and for a conv (stride 1) its input
    (h, w, c) and output (oh, ow)."""
    h, w, c = cfg["model"]["input"]
    out = []
    for layer in cfg["model"]["layers"]:
        if layer["kind"] == "conv":
            k = layer["kh"] * layer["kw"] * c
            oh = h + 2 * layer["padding"] - layer["kh"] + 1
            ow = w + 2 * layer["padding"] - layer["kw"] + 1
            out.append({**layer, "m": batch * oh * ow, "k": k,
                        "n": layer["c_out"], "hwc": (h, w, c),
                        "out_hw": (oh, ow)})
            h, w, c = oh // layer["pool"], ow // layer["pool"], layer["c_out"]
        else:
            k = h * w * c
            out.append({**layer, "m": batch, "k": k, "hwc": None})
            h, w, c = 1, 1, layer["n"]
    return out


def gemms(cfg: dict, batch: int) -> List[tuple]:
    """(m, k, n) of every CIM GEMM that one batch of images needs."""
    return [(l["m"], l["k"], l["n"]) for l in layers(cfg, batch)]


def make_params(cfg: dict, pt: cim.Point, seed: int):
    """Every layer's parameters, made on the device in one call."""
    shapes = [(l["k"], l["n"]) for l in layers(cfg, 1)]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [cim.init_linear(kk, k, n, pt, cfg["max_gamma"])
                for kk, (k, n) in zip(keys, shapes)]

    return make(key_of(seed, 2))


def _patches(x: jnp.ndarray, kh: int, kw: int, pad: int) -> jnp.ndarray:
    """(B, H, W, C) -> (B*OH*OW, kh*kw*C), channel fastest (stride 1)."""
    b, h, w, c = x.shape
    oh, ow = h + 2 * pad - kh + 1, w + 2 * pad - kw + 1
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    cols = [xp[:, i:i + oh, j:j + ow, :] for i in range(kh)
            for j in range(kw)]
    return jnp.concatenate(cols, axis=-1).reshape(b * oh * ow, kh * kw * c)


def reference(cfg: dict, pt: cim.Point, params, images: jnp.ndarray,
              dtype=jnp.float32) -> jnp.ndarray:
    """(B, H, W, C) images -> (B, classes) outputs, computed in `dtype`."""
    x = images.astype(dtype)
    b = x.shape[0]
    for layer, p in zip(layers(cfg, b), params):
        if layer["kind"] == "conv":
            h, w = layer["out_hw"]
            y = cim.linear(_patches(x, layer["kh"], layer["kw"],
                                    layer["padding"]), p, pt,
                           cfg["max_gamma"], dtype=dtype)
            y = y.reshape(b, h, w, layer["n"])
        else:
            y = cim.linear(x.reshape(b, -1), p, pt, cfg["max_gamma"],
                           dtype=dtype)
        if layer["activation"] == "relu":
            y = jnp.maximum(y, 0)
        s = layer["pool"]
        if s > 1:
            y = y.reshape(b, y.shape[1] // s, s, y.shape[2] // s, s,
                          y.shape[3]).max(axis=(2, 4))
        x = y
    return x
