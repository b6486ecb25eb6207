"""`cim_mbiw` kernel events per step of the server in the traced window:
a served batch of a CNN, a decode step of an LM (the server's `STEP`)."""


def read(r: dict):
    step = r["step"]
    n = r["units"].get(step, 0)
    if not n:
        return None
    by_span = r["trace"]["kernels"][r["kernel"]]["count_by_span"]
    return (by_span.get(f"bench.{step}", 0)
            + by_span.get(f"bench.{step}.fetch", 0)) / n
