"""The one-pass sweep: CountSketch SX += S·X as the scatter-add it is (a sign
multiply and an add per element), the emitted rows z = √w·X, and the same
directional extremes as the two-pass pass 2."""
from chipbench.costs.shapes import build_shapes


def flops(cfg: dict, traffic: dict, model) -> float:
    s = build_shapes(cfg, traffic, model)
    return 3.0 * s["n"] * s["D"] + 2.0 * s["rows"] * s["d"] * s["m"]


def bytes(cfg: dict, traffic: dict, model) -> float:
    s = build_shapes(cfg, traffic, model)
    rows_in = s["n"] * s["D"] + s["rows"] * s["d"] + 2 * s["n"]  # X, P, plan
    z_out = s["n"] * s["D"]
    per_chunk = s["m"] * (s["d"] + 4) + 2 * s["sketch"] * s["D"]  # net, extremes, SX
    return 4.0 * (rows_in + z_out + s["chunks"] * per_chunk)
