"""The coreset build's stage spans, as a profiler records them.

Each stage of a build is one ``jax.profiler.TraceAnnotation`` on the host:
``repro.build`` around the whole call, the stages as siblings inside it, so
no instant of a build falls under two stages, and every span that moves
data between host and device carries a ``bytes`` stat. The spans are always
on, so the coreset must not depend on whether a profiler records them.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import mctm as M
from repro.core.bernstein import DataScaler
from repro.core.coreset import build_coreset
from repro.core.distributed_coreset import distributed_build_coreset
from repro.data.dgp import generate
from repro.utils.compat import make_mesh

N, K, CHUNK = 3001, 200, 512
BUILD = "repro.build"
COMMON = {"repro.build.engine", "repro.build.put_rows", "repro.scoring.stage",
          "repro.scoring.gather.hull", "repro.scoring.gather.scores",
          "repro.scoring.projection", "repro.scoring.directions",
          "repro.scoring.finalize", "repro.coreset.sample", "repro.coreset.hull_points"}
STAGES = {
    "two_pass": COMMON | {"repro.scoring.pass1", "repro.scoring.gather.gram",
                          "repro.scoring.gather.moments", "repro.scoring.pass2"},
    "one_pass": COMMON | {"repro.scoring.plan", "repro.scoring.sweep",
                          "repro.scoring.gather.sketch", "repro.scoring.readoff"},
}
TRANSFER = ("repro.build.put_rows", "repro.scoring.stage", "repro.scoring.gather.")


def _host_spans(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


@pytest.fixture(scope="module")
def data():
    Y = np.asarray(generate("normal_mixture", N, seed=3), np.float32)
    cfg = M.MCTMConfig(J=2, degree=6)
    return cfg, DataScaler.fit(Y), Y


def _build(data, strategy):
    cfg, scaler, Y = data
    key = jax.random.PRNGKey(7)
    if strategy == "single_host":
        return build_coreset(cfg, scaler, Y, K, key=key, chunk_size=CHUNK)
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
    sketch = 4 * 14 * 14 if strategy == "one_pass" else 0
    return distributed_build_coreset(cfg, scaler, Y, K, mesh=mesh, key=key,
                                     chunk_size=CHUNK, sketch_size=sketch)


def _traced(data, strategy, tmp_path):
    untraced = _build(data, strategy)
    with jax.profiler.trace(str(tmp_path)):
        traced = _build(data, strategy)
    for field in ("indices", "weights", "scores"):
        np.testing.assert_array_equal(getattr(traced, field), getattr(untraced, field))
    return _host_spans(str(tmp_path))


@pytest.mark.parametrize("strategy", ["two_pass", "one_pass"])
def test_build_stages_are_disjoint_spans_inside_the_build(data, strategy, tmp_path):
    spans = _traced(data, strategy, tmp_path)
    builds = [s for s in spans if s[0] == BUILD]
    assert len(builds) == 1
    _, b0, b1, _ = builds[0]
    stages = sorted((s for s in spans if s[0] != BUILD), key=lambda s: s[1])
    assert {s[0] for s in stages} == STAGES[strategy]
    for name, s0, s1, stats in stages:
        assert b0 <= s0 <= s1 <= b1, name
        if name.startswith(TRANSFER):
            assert stats.get("bytes", 0) > 0, name
    for prev, nxt in zip(stages, stages[1:]):
        assert prev[2] <= nxt[1], (prev[0], nxt[0])


def test_single_host_build_shares_the_sampling_spans(data, tmp_path):
    names = {s[0] for s in _traced(data, "single_host", tmp_path)}
    assert names == {"repro.coreset.sample", "repro.coreset.hull_points"}


def test_transfer_bytes_are_the_arrays_moved(data, tmp_path):
    spans = {s[0]: s[3] for s in _traced(data, "two_pass", tmp_path)}
    n_pad = -(-N // CHUNK) * CHUNK
    assert spans["repro.build.put_rows"]["bytes"] == N * 2 * 4
    assert spans["repro.scoring.stage"]["bytes"] == n_pad * (2 * 4 + 2 * 4)
    assert spans["repro.scoring.gather.scores"]["bytes"] == n_pad * 4
    assert spans["repro.scoring.gather.gram"]["bytes"] == 14 * 14 * 4
