"""Proof that the CIM engine runs on a TPU, end to end, with compiled kernels.

    python chip_smoke.py              # one chip: phases `lenet` and `olmo`
    python chip_smoke.py --chips 4    # four chips: phase `sharded` only

Phase `lenet` serves the paper's LeNet (28x28x1 -> 3x3 convs to 16 and 32
channels -> two dense layers) on 1024 pseudo-MNIST images through
`models.cnn.lenet_program(...).bind(...).serve` at (r_in, r_w) = (8, 4),
(4, 2) and (1, 1) — the nibble-serial two-plane walk, the one-plane walk
and the bit-serial walk — and requires the served output to equal
`bound.reference` bit for bit and the served executable to hold the
Mosaic kernel (`tpu_custom_call`).

Phase `olmo` runs `repro.launch.serve` in this process on olmo-1b at its
published widths in `--cim-mode engine` (4 requests, prompt 32, 8 new
tokens, float32 activations, `--assert-no-recompile`), then requires the
engine-mode prefill logits to equal the fakequant path's bit for bit and
every generated token to be a vocabulary id.  It also reports how far the
ring decode attention kernel lands from its jnp oracle at olmo's heads.

Phase `sharded` (`--chips 4`) serves the LeNet program and one olmo-1b
2048 -> 8192 projection program with `ShardingConfig(devices=4)` and
requires each to equal the same program on one device bit for bit, with
its output spread over the four devices.

Weights and data are random, made from `--seed`.  The last line of
standard output is `{"ok": true, "device": {...}}`; on any failure, when
JAX finds no TPU, or when the `repro` sources are not next to this file,
the script prints no such line and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

LENET_POINTS = ((8, 4), (4, 2), (1, 1))
LENET_BATCH = 1024


def _equal(a, b) -> bool:
    import numpy as np
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def _mismatch(a, b) -> str:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (f"{int(np.sum(a != b))}/{a.size} entries differ, max |diff| "
            f"{float(np.max(np.abs(a.astype(np.float64) - b))):.3g}")


def phase_lenet(batch: int, seed: int) -> None:
    """Serve LeNet at each operating point; codes == reference, bitwise."""
    import jax
    import jax.numpy as jnp

    from repro.core.cim_layers import CIMConfig
    from repro.data.pseudo_mnist import make_dataset
    from repro.models import cnn

    images = jnp.asarray(make_dataset(n_train=batch, n_test=1,
                                      seed=seed)[0][..., None])
    params = cnn.lenet_params_list(cnn.init_lenet(jax.random.PRNGKey(seed)))
    bad = []
    for r_in, r_w in LENET_POINTS:
        cim = CIMConfig(mode="engine", r_in=r_in, r_w=r_w)
        bound = cnn.lenet_program(batch=batch, cim=cim).bind(params)
        t0 = time.time()
        out = bound.serve(images).block_until_ready()
        first_s = time.time() - t0
        reps = 5
        t0 = time.time()
        for _ in range(reps):
            bound.serve(images).block_until_ready()
        per_s = (time.time() - t0) / reps
        ref = bound.reference(images)
        exact = _equal(out, ref)
        kernel = "tpu_custom_call" in jax.jit(bound.serve).lower(
            images).as_text()
        print(f"lenet r_in={r_in} r_w={r_w}: {batch / per_s:.1f} images/s "
              f"(batch {batch}, {per_s * 1e3:.2f} ms/batch; first call "
              f"{first_s:.1f}s incl. compile); output == reference: "
              f"{exact}; tpu_custom_call: {kernel}", flush=True)
        if not exact:
            print(f"  lenet ({r_in},{r_w}) mismatch: {_mismatch(out, ref)}")
        if not (exact and kernel):
            bad.append((r_in, r_w))
    if bad:
        raise AssertionError(f"lenet failed at operating points {bad}")


def phase_olmo(seed: int, arch_args=("--arch", "olmo-1b")) -> None:
    """Serve olmo-1b through launch.serve in engine mode; prefill logits ==
    fakequant's, bitwise; generated ids in the vocabulary."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attn.ops import (ring_decode_attention,
                                              ring_decode_attention_ref)
    from repro.launch import serve
    from repro.models import transformer as tf

    batch, prompt_len, gen_len = 4, 32, 8
    res = serve.main([*arch_args, "--cim-mode", "engine",
                      "--dtype", "float32", "--batch", str(batch),
                      "--prompt-len", str(prompt_len),
                      "--gen-len", str(gen_len), "--assert-no-recompile",
                      "--seed", str(seed)])
    cfg = res["cfg"]
    print(f"olmo: {cfg.name} d={cfg.d_model} heads={cfg.n_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}, {cfg.n_layers} layers "
          f"served (no depth cut); prefill incl. compile "
          f"{res['prefill_s']:.1f}s, decode warmup incl. compile "
          f"{res['warmup_s']:.1f}s, {res['decode_steps']} decode steps "
          f"{batch * res['decode_steps'] / res['decode_s']:.1f} tok/s",
          flush=True)
    toks = np.asarray(res["tokens"])     # prefill's token + gen_len decoded
    valid = toks.shape == (batch, gen_len + 1) and bool(
        np.all((toks >= 0) & (toks < cfg.vocab_size)))

    cfg_fq = cfg.replace(cim=dataclasses.replace(cfg.cim, mode="fakequant"))
    cache = tf.init_cache(cfg_fq, batch, max_len=prompt_len + gen_len + 8)
    logits_fq, _, _ = tf.forward(cfg_fq, res["params"], res["prompt"],
                                 cache=cache)
    parity = _equal(res["prefill_logits"], logits_fq)
    finite = bool(jnp.all(jnp.isfinite(res["prefill_logits"])))
    print(f"olmo: engine prefill logits == fakequant: {parity}; finite: "
          f"{finite}; generated ids valid: {valid} {toks.shape}", flush=True)
    if not parity:
        print(f"  olmo logits mismatch: "
              f"{_mismatch(res['prefill_logits'], logits_fq)}")

    # ring decode attention at olmo's heads, against its jnp oracle
    hd = cfg.d_model // cfg.n_heads
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = (batch, 16, cfg.n_heads, hd)
    q = jax.random.normal(ks[0], (batch, cfg.n_heads, hd))
    k, v = (jax.random.normal(kk, shape) for kk in ks[1:3])
    bias = jnp.where(jax.random.uniform(ks[3], (batch, 16)) < 0.25,
                     -1e9, 0.0)
    got = ring_decode_attention(q, k, v, bias)
    want = ring_decode_attention_ref(q, k, v, bias)
    err = float(jnp.max(jnp.abs(got - want)))
    print(f"ring decode attention (R={batch} L=16 H={cfg.n_heads} "
          f"hd={hd}) vs oracle: bitwise equal {_equal(got, want)}, max "
          f"|diff| {err:.3g}", flush=True)
    # the two compilers' exp and reduction orders may differ in the last
    # bits; a kernel that computed something else would miss by far more
    if not (parity and finite and valid and err < 1e-4):
        raise AssertionError("olmo phase failed")


def phase_sharded(batch: int, seed: int, devices: int = 4,
                  proj=(2048, 8192)) -> None:
    """LeNet and one olmo-1b projection on a `devices` mesh == one device."""
    import jax

    from repro.core import mapping
    from repro.core.cim_layers import CIMConfig, _engine_config
    from repro.data.pseudo_mnist import make_dataset
    from repro.models import cnn
    from repro.runtime import ShardingConfig
    from repro.runtime.program import compile_program

    sharding = ShardingConfig(devices=devices)
    ok = True

    def check(name, one, many):
        nonlocal ok
        devs = getattr(many, "sharding").device_set
        exact = _equal(one, many)
        print(f"sharded {name}: {devices}-device output == 1-device: "
              f"{exact}; output on {len(devs)} devices "
              f"({many.sharding})", flush=True)
        if not exact:
            print(f"  {name} mismatch: {_mismatch(one, many)}")
        ok &= exact and len(devs) == devices

    images = jax.numpy.asarray(make_dataset(n_train=batch, n_test=1,
                                            seed=seed)[0][..., None])
    params = cnn.lenet_params_list(cnn.init_lenet(jax.random.PRNGKey(seed)))
    cim = CIMConfig(mode="engine")
    outs = [cnn.lenet_program(batch=batch, cim=c).bind(params)
            .serve(images).block_until_ready()
            for c in (cim, cim.replace(sharding=sharding))]
    check("lenet", *outs)

    k, n = proj
    rows = 128
    spec = mapping.LayerSpec(m=rows, k=k, n=n, r_in=8, r_w=4)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (rows, k))
    progs = [compile_program([spec], _engine_config(c))
             for c in (cim, cim.replace(sharding=sharding))]
    p = progs[0].init_params(jax.random.PRNGKey(seed))
    outs = [pr.bind(p).serve(x).block_until_ready() for pr in progs]
    check(f"projection {k}->{n}", *outs)
    if not ok:
        raise AssertionError("sharded phase failed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devs[0].platform!r})", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devs)} found",
              file=sys.stderr)
        return 1
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(LENET_BATCH, args.seed))]
    else:
        phases = [("lenet", lambda: phase_lenet(LENET_BATCH, args.seed)),
                  ("olmo", lambda: phase_olmo(args.seed))]
    failed = []
    for name, run in phases:
        t0 = time.time()
        try:
            run()
        except (Exception, SystemExit):
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAIL' if name in failed else 'PASS'} "
              f"({time.time() - t0:.1f}s)", flush=True)
    print(f"compile cache: {cache_events['hits']} hits, "
          f"{cache_events['misses']} misses", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
