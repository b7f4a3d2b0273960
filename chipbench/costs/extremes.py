"""Pass 2 of the two-pass build: the running max and min of ⟨p, v⟩ for each
of the m directions over the n·J derivative rows of width d."""
from chipbench.costs.shapes import build_shapes


def flops(cfg: dict, traffic: dict, model) -> float:
    s = build_shapes(cfg, traffic, model)
    return 2.0 * s["rows"] * s["d"] * s["m"]


def bytes(cfg: dict, traffic: dict, model) -> float:
    s = build_shapes(cfg, traffic, model)
    # the rows once, the net and the four (m,) results once per chunk
    return 4.0 * (s["rows"] * s["d"] + s["chunks"] * s["m"] * (s["d"] + 4))
