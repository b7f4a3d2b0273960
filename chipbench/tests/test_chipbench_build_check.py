"""The build cells' check on the CPU at a tiny size: a sound run is correct,
and a run with the timed path broken underneath, or the control in the
program's place, is not.

Each test skips the look for a chip and drives the rest of a run
(``run.run_cell(on_chip=False)``) on a tiny cell added as new files."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_testlib as lib  # noqa: E402

from chipbench import run  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.tiny_bench(tmp_path_factory.mktemp("bench"))


def test_score_stats_by_hand():
    entry = run.load_module(os.path.join(lib.BENCH, "entries", "build.py"), "entry_build")
    n = 4
    u_ref = np.array([0.1, 0.2, 0.3, 0.4]) - 1.0 / n  # reference p = 0.1 … 0.4
    scores = np.array([0.2, 0.2, 0.3, 0.3])  # program p = 0.2, 0.2, 0.3, 0.3
    idx = np.array([0, 3])
    w_ref = 1.0 / (2 * np.array([0.1, 0.4]))  # 5, 1.25
    w = w_ref + np.array([0.5, 0.0])
    stats = entry.score_stats(scores, u_ref, idx, w, k_sample=2)
    assert stats["lev_tv"] == pytest.approx(0.5 * (0.1 + 0.0 + 0.0 + 0.1))
    assert stats["w_dev"] == pytest.approx(0.5 / 6.25)
    # reference cumulative distribution 0.1, 0.3, 0.6, 1.0: rows 0 and 3 own
    # [0, 0.1] and [0.6, 1.0]
    assert entry.draw_gap(idx, np.array([0.05, 0.7]), u_ref) == 0.0
    assert entry.draw_gap(idx, np.array([0.15, 0.5]), u_ref) == pytest.approx(0.1)


@pytest.mark.parametrize("workload", ["tiny.build.two_pass", "tiny.build.one_pass"])
def test_sound_build_is_correct(root, workload):
    rec = lib.run_tiny(root, workload)
    assert rec["correct"], rec["checks"]
    assert rec["attempted"] >= 1 and rec["failed"] == 0
    assert rec["metrics"]["build_rows_per_s"]["value"] > 0
    assert list(rec)[-1] == "checks"


def test_half_the_rows_left_out_is_caught(root, monkeypatch):
    """Pass 1 accumulates the Gram of every other row, doubled."""
    from repro.core import distributed_coreset as dc

    real = dc.pass1_update

    def half(G, s1, s2, X, P, sw, gram_dtype=None):
        keep = (np.arange(X.shape[0]) % 2 == 0).astype(np.float32) * np.sqrt(2.0)
        return real(G, s1, s2, X, P, sw * keep, gram_dtype=gram_dtype)

    monkeypatch.setattr(dc, "pass1_update", half)
    rec = lib.run_tiny(root, "tiny.build.two_pass")
    assert not rec["correct"]
    assert rec["checks"]["lev_tv"]["value"] > rec["checks"]["lev_tv"]["limit"]


@pytest.mark.parametrize("where", ["weight", "hull_point", "draw", "draw_half", "draw_key"])
def test_an_altered_answer_is_caught(root, monkeypatch, where):
    import jax
    import jax.numpy as jnp

    from repro.core import distributed_coreset as dc

    real = dc.distributed_build_coreset

    def altered(*a, **kw):
        cs = real(*a, **kw)
        k_sample = int(0.8 * cs.size)
        n = cs.scores.size
        p = cs.scores / cs.scores.sum()
        if where in ("draw_half", "draw_key"):
            # half the rows left out of the draw, or the draw from the
            # build's own key; either way weighted 1/(k·p) like a sound draw
            if where == "draw_half":
                idx = cs.indices[:k_sample] % (n // 2)
            else:
                idx = np.asarray(jax.random.choice(kw["key"], n, (k_sample,), p=jnp.asarray(p)))
            cs.indices[:k_sample] = idx
            cs.weights[:k_sample] = 1.0 / (k_sample * p[idx])
        elif where == "weight":
            # the heaviest drawn row's weight doubled (a doubled light
            # weight moves the coreset's mass too little for any check)
            cs.weights[np.argmax(cs.weights[:k_sample])] *= 2.0
        elif where == "hull_point":
            # the first hull point holds the first direction's extreme
            cs.indices[k_sample] = int(np.argmin(cs.scores))
        else:
            cs.indices[0] = (cs.indices[0] + cs.scores.size // 2) % cs.scores.size
        return cs

    monkeypatch.setattr(dc, "distributed_build_coreset", altered)
    rec = lib.run_tiny(root, "tiny.build.two_pass")
    assert not rec["correct"], rec["checks"]
    if where.startswith("draw_"):
        # the weights still fit the scores: only the draw gives it away
        gap = rec["checks"]["draw_gap"]
        assert gap["value"] > 10 * gap["limit"], rec["checks"]
        assert rec["checks"]["w_dev"]["value"] <= rec["checks"]["w_dev"]["limit"]


@pytest.mark.parametrize("workload", ["tiny.build.two_pass", "tiny.build.one_pass"])
def test_control_is_not_correct(root, workload):
    """The reference at three bf16 passes, put in the program's place."""
    cell = run.find_cell(workload, root=root)
    run.import_program(lib.ROOT)
    ctx = run.RunContext(cell, 3, 0.0, False)
    ctx.state = cell.entry.setup(ctx)
    ctx.results = [cell.entry.control_call(ctx, ctx.state, i) for i in range(2)]
    ok, checks = run.judge(ctx, cell.entry.check(ctx, ctx.state, ctx.results,
                                                 np.random.default_rng(3)))
    assert not ok
    assert checks["lev_tv"]["value"] > 3 * checks["lev_tv"]["limit"]
    assert checks["draw_gap"]["value"] > 3 * checks["draw_gap"]["limit"]
