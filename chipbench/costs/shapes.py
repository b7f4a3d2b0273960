"""Shapes one coreset build works on, from the configuration and the mix.

n rows of J columns; D = J·d basis columns (d = degree + 1); n·J derivative
rows of width d; m hull directions (the random net plus ± the d axes). The
cost functions beside this file count what the algorithm needs at these
shapes: no lane padding, no one-hot sketch matmul, and one pass per
product however many the chip's f32 emulation takes.
"""

# random hull directions per hull point: the ``hull_oversample`` default of
# ``DistributedScoringEngine``, which ``distributed_build_coreset`` does not
# expose, so every build runs it
HULL_OVERSAMPLE = 4


def build_shapes(cfg: dict, traffic: dict) -> dict:
    J, d = cfg["J"], cfg["degree"] + 1
    k = cfg["k"]
    k_hull = k - int(cfg["alpha"] * k)
    m = max(HULL_OVERSAMPLE * k_hull, 8) + 2 * d
    n = cfg["n"]
    D = J * d
    return {"n": n, "J": J, "d": d, "D": D, "m": m, "rows": n * J,
            "chunks": -(-n // cfg["chunk"]),
            "sketch": int(traffic.get("sketch_factor", 0)) * D * D}
