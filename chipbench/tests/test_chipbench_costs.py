"""The cost functions against counts made by hand at J=2 and J=10."""
from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as lib  # noqa: E402

from chipbench import run  # noqa: E402


def _cfg(name):
    with open(os.path.join(lib.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _cost(kernel):
    return run.load_module(os.path.join(lib.BENCH, "costs", kernel + ".py"), "cost_" + kernel)


MCTM = run.load_module(os.path.join(lib.BENCH, "models", "mctm.py"), "model_mctm")


TWO = {"sketch_factor": 0}
ONE = {"sketch_factor": 4}

# J=2: n = 2^24+1, d = 7, D = 14, 2n derivative rows, k_hull = 400, so
# m = 4·400 + 2·7 = 1614 directions, ⌈n/16384⌉ = 1025 chunks
N2, N10 = 2**24 + 1, 2**22 + 1


def test_gram_by_hand():
    g = _cost("gram")
    assert g.flops(_cfg("mctm_j2_mixture"), TWO, MCTM) == 2 * N2 * 14 * 14
    assert g.bytes(_cfg("mctm_j2_mixture"), TWO, MCTM) == 4 * (N2 * 14 + 1025 * 196)
    # J=10: D = 70, ⌈(2^22+1)/16384⌉ = 257 chunks
    assert g.flops(_cfg("mctm_j10_covertype"), TWO, MCTM) == 2 * N10 * 70 * 70
    assert g.bytes(_cfg("mctm_j10_covertype"), TWO, MCTM) == 4 * (N10 * 70 + 257 * 4900)


def test_extremes_by_hand():
    e = _cost("extremes")
    assert e.flops(_cfg("mctm_j2_mixture"), TWO, MCTM) == 2 * (2 * N2) * 7 * 1614
    assert e.bytes(_cfg("mctm_j2_mixture"), TWO, MCTM) == 4 * (2 * N2 * 7 + 1025 * 1614 * 11)
    assert e.flops(_cfg("mctm_j10_covertype"), TWO, MCTM) == 2 * (10 * N10) * 7 * 1614
    # ≈ 0.76 TFLOP a build at J=2: 3.9 ms at 197 TFLOP/s, compute-bound
    assert e.flops(_cfg("mctm_j2_mixture"), TWO, MCTM) / 197e12 == pytest.approx(3.85e-3, rel=0.01)


def test_sweep_counts_the_sketch_as_a_scatter_add():
    s = _cost("sweep")
    cfg = _cfg("mctm_j2_mixture")
    extremes = 2 * (2 * N2) * 7 * 1614
    assert s.flops(cfg, ONE, MCTM) == 3 * N2 * 14 + extremes
    # no sketch-sized factor per row: a one-hot matmul would add 2·n·784·14
    assert s.flops(cfg, ONE, MCTM) < extremes * 1.001
    rows_in = N2 * 14 + 2 * N2 * 7 + 2 * N2
    per_chunk = 1614 * 11 + 2 * 784 * 14
    assert s.bytes(cfg, ONE, MCTM) == 4 * (rows_in + N2 * 14 + 1025 * per_chunk)


def test_whole_build_by_hand():
    b = _cost("build")
    cfg = _cfg("mctm_j2_mixture")
    basis = 8 * 2 * N2 * 7 * 2
    two = basis + 2 * N2 * 196 + (2 * N2 * 196 + 2 * N2 * 14) + 2 * 2 * N2 * 7 * 1614
    assert b.flops(cfg, TWO, MCTM) == two
    one = basis / 2 + 3 * N2 * 14 + (2 * N2 * 196 + 2 * N2 * 14) + 2 * 2 * N2 * 7 * 1614
    assert b.flops(cfg, ONE, MCTM) == one
    cfg10 = _cfg("mctm_j10_covertype")
    ten = (8 * 10 * N10 * 7 * 2 + 2 * N10 * 4900 + (2 * N10 * 4900 + 2 * N10 * 70)
           + 2 * 10 * N10 * 7 * 1614)
    assert b.flops(cfg10, TWO, MCTM) == ten


FIT = {"steps": 400}


def test_whole_fit_by_hand():
    """Per step one value-and-gradient and one Hessian-vector sweep over the
    k = 2000 coreset rows: basis 16·k·J·d each; value-and-gradient 8·k·J·d +
    6·k·J² of products; the HVP adds 8·k·J·d + 12·k·J² of tangents."""
    f = _cost("fit")
    # J=2, d=7: k·J·d = 28,000, k·J² = 8,000
    vg2 = 24 * 28_000 + 6 * 8_000
    hvp2 = vg2 + 8 * 28_000 + 12 * 8_000
    assert (vg2, hvp2) == (720_000, 1_040_000)
    assert f.flops(_cfg("mctm_j2_mixture"), FIT) == 400 * 1_760_000
    # J=10, d=7: k·J·d = 140,000, k·J² = 200,000
    vg10 = 24 * 140_000 + 6 * 200_000
    hvp10 = vg10 + 8 * 140_000 + 12 * 200_000
    assert (vg10, hvp10) == (4_560_000, 8_080_000)
    assert f.flops(_cfg("mctm_j10_covertype"), FIT) == 400 * 12_640_000
    # ≈ 5.06 GFLOP a fit: 25.7 µs at 197 TFLOP/s
    assert f.flops(_cfg("mctm_j10_covertype"), FIT) / 197e12 == pytest.approx(2.57e-5, rel=0.01)
