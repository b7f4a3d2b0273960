"""Pass 1 of the two-pass build: G = XᵀX over the n basis rows of width D."""
from chipbench.costs.shapes import build_shapes


def flops(cfg: dict, traffic: dict, model) -> float:
    s = build_shapes(cfg, traffic, model)
    return 2.0 * s["n"] * s["D"] ** 2


def bytes(cfg: dict, traffic: dict, model) -> float:
    s = build_shapes(cfg, traffic, model)
    return 4.0 * (s["n"] * s["D"] + s["chunks"] * s["D"] ** 2)
