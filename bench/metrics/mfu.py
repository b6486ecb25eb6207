"""Model operations of the work done in the traced window, over the window
times the chip's int8 peak: useful and prompt tokens only, counted from
shapes by the configuration (bench/configs/<config>.py)."""


def read(r: dict):
    ops = r["work"].get("model_ops", 0)
    if not ops:
        return None
    return 100.0 * ops / (r["trace"]["window_s"]
                          * r["peaks"]["int8_ops_per_s"])
