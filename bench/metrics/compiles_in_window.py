"""Compiles, compile-cache loads and traces inside the window, with the
growth of the engine's plan and trace counters (runtime/engine.py)."""


def read(r: dict):
    return float(r["compiles_in_window"])
