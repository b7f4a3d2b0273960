"""Shapes one coreset build works on, from the configuration, the mix and
the configuration's model (``chipbench/models/<model>.py``).

n points of J response columns; a leverage row of D columns per point (the
model's width: J·d basis values for the MCTM, d = degree + 1); n·J
derivative rows of width d; m hull directions (the random net plus ± the d
axes). The cost functions beside this file count what the algorithm needs
at these shapes: no lane padding, no one-hot sketch matmul, and one pass
per product however many the chip's f32 emulation takes.
"""

# random hull directions per hull point: the ``hull_oversample`` default of
# ``DistributedScoringEngine``, which ``distributed_build_coreset`` does not
# expose, so every build runs it
HULL_OVERSAMPLE = 4


def build_shapes(cfg: dict, traffic: dict, model) -> dict:
    w = model.widths(cfg)
    J, d, D = w["J"], w["d"], w["D"]
    k = cfg["k"]
    k_hull = k - int(cfg["alpha"] * k)
    m = max(HULL_OVERSAMPLE * k_hull, 8) + 2 * d
    n = cfg["n"]
    return {"n": n, "J": J, "d": d, "D": D, "m": m, "rows": n * J,
            "chunks": -(-n // cfg["chunk"]),
            "sketch": int(traffic.get("sketch_factor", 0)) * D * D}
