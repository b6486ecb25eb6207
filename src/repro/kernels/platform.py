"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted on
the CPU.

The choice is made here and nowhere else, from the platform the enclosing
program is lowered for (`jax.lax.platform_dependent`), never from a flag
or from the process's default backend.  A program lowered for a TPU —
including an ahead-of-time compile for a described chip — therefore
always carries the Mosaic kernel (`tpu_custom_call`) and never falls back
to the interpreter; a kernel Mosaic refuses raises at compile time.  On
the CPU the kernel runs through the Pallas interpreter.  Any other
platform has no branch and fails to lower.
"""
from __future__ import annotations

from typing import Any, Callable

import jax


def platform_call(build: Callable[[bool], Callable[..., Any]], *args):
    """Call the kernel that `build` makes for the lowering platform.

    Args:
      build: `interpret -> callable`, the `pl.pallas_call(...)` of one
        kernel built with `interpret=interpret` (the kernel body may also
        specialise on it, e.g. to pin float ops the interpreter's XLA
        backend could contract).
      *args: the kernel's operands.
    Returns:
      `build(False)(*args)` in a program lowered for a TPU,
      `build(True)(*args)` in one lowered for the CPU.
    """
    return jax.lax.platform_dependent(*args, cpu=build(True),
                                      tpu=build(False))
