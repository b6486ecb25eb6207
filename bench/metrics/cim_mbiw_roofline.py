"""The least time the traced window's CIM GEMMs need (the larger of their
operations at the int8 peak and their bytes at HBM bandwidth, from shapes
and precision alone: bench/cim.py), over the summed device time of the
`cim_mbiw` kernel's events."""
from cim import least_seconds


def read(r: dict):
    k = r["trace"]["kernels"].get(r["kernel"])
    if not k or not k["seconds"] or not r["work"].get("cim_ops"):
        return None
    least, _ = least_seconds(r["work"]["cim_ops"], r["work"]["cim_bytes"],
                             r["peaks"])
    return 100.0 * least / k["seconds"]
