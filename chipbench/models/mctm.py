"""The unconditional MCTM (paper Section 2): Bernstein marginals of degree
``degree`` on each of the J response columns, a unit lower-triangular Λ.

What a build cell needs to know of its model, for ``entries/build.py``,
``entries/fit.py`` and the cost functions:

- ``widths``: J response columns (derivative rows per point), d basis
  values per column, and D, the width of a point's leverage row, J·d;
- ``program`` and ``build``: the objects one build call is given, made once
  in set-up, and the call itself;
- ``reference``: the float64 leverage rows whose Gram, sketch and leverage
  the check reads, and the derivative rows whose directional extremes
  ``hull_gap`` checks;
- ``control``: the reference one precision down, in the program's place.
"""
from __future__ import annotations

import numpy as np

from chipbench import datagen
from chipbench import reference as R


def widths(cfg: dict) -> dict:
    J, d = cfg["J"], cfg["degree"] + 1
    return {"J": J, "d": d, "D": J * d}


def program(cfg: dict, Y: np.ndarray) -> dict:
    from repro.core import mctm as M
    from repro.core.bernstein import DataScaler

    return {"model": M.MCTMConfig(J=cfg["J"], degree=cfg["degree"], eta=cfg["eta"],
                                  min_slope=cfg["min_slope"]),
            "scaler": DataScaler.fit(Y)}


def build(cfg: dict, prog: dict, Y: np.ndarray, *, mesh, key, sketch_size: int):
    """One whole user call: float32 host rows in, ``CoresetResult`` out."""
    from repro.core import distributed_coreset

    return distributed_coreset.distributed_build_coreset(
        prog["model"], prog["scaler"], Y, cfg["k"], cfg["method"],
        mesh=mesh, key=key, alpha=cfg["alpha"], chunk_size=cfg["chunk"],
        sketch_size=sketch_size)


def reference(cfg: dict, Y: np.ndarray) -> tuple[R.Design, R.HullReference]:
    low, high = datagen.scaler_bounds(Y.astype(np.float64))
    return (R.Design(Y, low, high, cfg["degree"]),
            R.HullReference(Y, low, high, cfg["degree"]))


def control(cfg: dict, Y: np.ndarray, key, sketch_size: int) -> dict:
    from chipbench import control as C

    low, high = datagen.scaler_bounds(Y.astype(np.float64))
    return C.build({"Y": Y, "low": low, "high": high, "degree": cfg["degree"],
                    "k": cfg["k"], "alpha": cfg["alpha"], "chunk": cfg["chunk"],
                    "sketch_size": sketch_size}, key)
