"""One whole coreset build, for ``mfu.build``: the basis and its derivative
(about 8 operations per basis value), the accumulation pass (Gram, or the
sketch), the leverage read-off (X·V and the weighted square sum) and the
directional extremes."""
from chipbench.costs.shapes import build_shapes


def flops(cfg: dict, traffic: dict, model) -> float:
    s = build_shapes(cfg, traffic, model)
    n, D, rows, d, m = s["n"], s["D"], s["rows"], s["d"], s["m"]
    basis = 8.0 * rows * d * (1 if s["sketch"] else 2)
    accumulate = 3.0 * n * D if s["sketch"] else 2.0 * n * D * D
    readoff = 2.0 * n * D * D + 2.0 * n * D
    extremes = 2.0 * rows * d * m
    return basis + accumulate + readoff + extremes
