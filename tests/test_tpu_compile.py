"""The main path's Pallas kernels compiled for a described TPU v5e chip.

Nothing runs: the TPU compiler installed with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached, which catches what
interpret mode cannot (unaligned blocks, VMEM over-use, programs that do not
fit in HBM). Shapes are the paper-scale main path: J=2, degree 6 (D=14,
lane-padded to 128), 16,384-row chunks, ``block_rows`` 512, a 4·D² = 784-row
CountSketch, and the hull net of a k=2000 ``l2-hull`` coreset (4·400 = 1600
directions).

The topology is described inside the module fixture only: the TPU library
may be loaded by one process at a time, so describing it while a module is
imported would break collection under pytest-xdist.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bernstein.kernel import bernstein_kernel
from repro.kernels.extremes.kernel import (
    DEFAULT_BLOCK_ROWS,
    EXTREMES_BLOCK_ROWS,
    extremes_kernel,
)
from repro.kernels.gram.kernel import gram_kernel
from repro.kernels.sweep.kernel import sweep_kernel
from repro.kernels.sweep.ops import fused_sweep_update

J, DEGREE = 2, 6
d = DEGREE + 1
D = J * d                    # 14
LANE = 128
CHUNK = 16_384               # train_mctm --chunk default
SKETCH = 4 * D * D           # 784, the one-pass auto sketch
M_DIRS = 4 * (2000 - int(0.8 * 2000))  # hull net at k=2000, alpha=0.8
M_PAD = -(-M_DIRS // LANE) * LANE
HBM_BYTES = 16 * 1024**3     # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies

    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_and_check(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    )
    assert used < HBM_BYTES, used


def _sweep_operands(sh, *, hull: bool):
    f32, i32 = jnp.float32, jnp.int32
    return (
        _sds((CHUNK, LANE), f32, sh),              # x, D lane-padded
        _sds((CHUNK * J, LANE), f32, sh),          # p, r = J rows per point
        _sds((CHUNK, 1), f32, sh),                 # sqrt weights
        _sds((1, CHUNK), i32, sh),                 # sketch rows
        _sds((1, CHUNK), f32, sh),                 # sketch signs
        _sds((1, 1), i32, sh),                     # valid point count
        _sds((M_PAD, LANE), f32, sh) if hull else None,
        None,                                      # omega: identity
    )


def test_sweep_kernel_hull_and_z_compiles(one_chip):
    """The one-pass chunk body: sketch + emitted z + hull extremes."""
    fn = partial(sweep_kernel, sketch_rows=SKETCH, r=J, want_z=True,
                 block_rows=DEFAULT_BLOCK_ROWS)
    _compile_and_check(fn, *_sweep_operands(one_chip, hull=True))


def test_sweep_kernel_moments_compiles(one_chip):
    """The sketched two-pass pass 1: sketch + hull moments, nothing kept."""
    fn = partial(sweep_kernel, sketch_rows=SKETCH, r=J, want_z=False,
                 want_moments=True, block_rows=DEFAULT_BLOCK_ROWS)
    _compile_and_check(fn, *_sweep_operands(one_chip, hull=False))


@pytest.mark.parametrize("rows", [CHUNK * J, CHUNK * 10])
def test_extremes_kernel_compiles(one_chip, rows):
    """The two-pass pass-2 hull reduction over one chunk's derivative rows,
    at J=2 and J=10: the score tile and the four (M_PAD, 128) VMEM
    accumulators fit the chip's scoped VMEM."""
    f32 = jnp.float32
    fn = partial(extremes_kernel, block_rows=EXTREMES_BLOCK_ROWS)
    _compile_and_check(
        fn,
        _sds((rows, LANE), f32, one_chip),
        _sds((M_PAD, LANE), f32, one_chip),
        _sds((1, 1), jnp.int32, one_chip),
    )


def test_gram_kernel_compiles(one_chip):
    """The two-pass pass-1 Gram of one chunk."""
    _compile_and_check(gram_kernel, _sds((CHUNK, LANE), jnp.float32, one_chip))


def test_bernstein_kernel_compiles(one_chip):
    """Basis + derivative of one chunk's J·CHUNK scaled values."""
    fn = partial(bernstein_kernel, degree=DEGREE)
    _compile_and_check(fn, _sds((CHUNK * J // LANE, LANE), jnp.float32, one_chip))


@pytest.mark.parametrize("rows", [CHUNK, 300])
def test_fused_sweep_ops_wrapper_compiles(one_chip, rows):
    """The jitted ops wrapper at unpadded shapes, forced onto the Pallas
    backend (``default_sweep_backend()`` sees the CPU here): its padding and
    ``block_rows`` clamp must produce blocks the chip accepts, for a full
    chunk and for a ragged one smaller than a block."""
    f32, i32 = jnp.float32, jnp.int32
    fn = partial(fused_sweep_update, backend="pallas")
    args = (
        _sds((SKETCH, D), f32, one_chip),
        _sds((rows, D), f32, one_chip),
        _sds((rows * J, d), f32, one_chip),
        _sds((rows,), f32, one_chip),
        _sds((rows,), i32, one_chip),
        _sds((rows,), f32, one_chip),
    )
    _compile_and_check(fn, *args, dirs=_sds((M_DIRS, d), f32, one_chip),
                       mask=_sds((rows,), f32, one_chip))


def test_one_pass_program_names_the_sweep_kernel(one_chip, monkeypatch):
    """The sharded one-pass program, compiled with the Pallas sweep as on the
    chip: the kernel's custom call is named after its jitted wrapper
    (``%_sweep_pallas.N``), and the benchmark's matcher counts it as the
    sweep kernel."""
    import os
    import sys

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro.kernels.sweep.ops as sweep_ops
    from repro.core import mctm as M
    from repro.core.bernstein import DataScaler
    from repro.core.distributed_coreset import make_sharded_onepass_fn
    from repro.core.scoring import _mctm_featurize

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from chipbench import roofline

    # the backend the chip would pick (``default_sweep_backend()`` sees the CPU)
    monkeypatch.setattr(sweep_ops, "default_sweep_backend", lambda: "pallas")
    Y = np.random.default_rng(0).standard_normal((100, J)).astype(np.float32)
    featurize = _mctm_featurize(M.MCTMConfig(J=J, degree=DEGREE), DataScaler.fit(Y))
    mesh = Mesh(np.array(list(one_chip.device_set)), ("data",))
    cps = 2
    fn = make_sharded_onepass_fn(featurize, mesh, ("data",), chunk=CHUNK,
                                 chunks_per_shard=cps, rows_per_point=J, hull=True,
                                 D=D, q=None, sketch_size=SKETCH)
    n = CHUNK * cps
    row, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    args = (_sds((n, J), jnp.float32, NamedSharding(mesh, P("data", None))),
            _sds((n,), jnp.float32, row), _sds((n,), jnp.float32, row),
            _sds((n,), jnp.int32, row), _sds((n,), jnp.float32, row),
            _sds((M_DIRS, d), jnp.float32, rep))
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    calls = [ln.strip() for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    sweeps = [c for c in calls if c.startswith("%_sweep_pallas")]
    assert sweeps, calls
    assert all(roofline.is_kernel("sweep", c) for c in sweeps)
