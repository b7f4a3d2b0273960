"""Distributed coreset construction over a device mesh (shard_map).

The scalable realization of the paper's Algorithm 1 on a TPU pod — the
sharded counterpart of ``repro.core.scoring.ScoringEngine``. Two layers:

Primitive collectives (building blocks, whole-shard bodies):
  * ``distributed_gram`` / ``distributed_leverage`` — per-shard Gram, one
    (dJ)² psum, local projections.
  * ``distributed_scoring_stats`` — one-collective psum of the scoring
    engine's full pass-1 state (Gram + hull moments).
  * ``distributed_direction_argmax`` — per-shard argmax ⟨p, v⟩ → global max
    via all_gather of (score, index) pairs. Ragged inputs (n not a multiple
    of the shard count) are padded to a shard multiple with −inf scores, so
    returned indices are exact for any n ≥ 1.

``DistributedScoringEngine`` — the fully distributed Algorithm 1. It fuses
the single-host engine's chunk loop INTO the shard_map body: each shard
scans its local rows chunk-by-chunk (``lax.scan`` over ``chunks_per_shard``
slices), reusing the exact per-chunk math of the single-host engine
(``pass1_update`` / ``leverage_chunk`` / ``hull_chunk_extremes``), so

  memory:  per-chip peak is O(chunk·J·d) — no (n, J, d) basis tensor and no
           full-shard score block ever materializes; carried state is the
           strategy's O((Jd)²)-ish statistics plus the (m,) running hull
           extremes (one-pass additionally keeps its per-shard retained z
           rows, O(per_shard·q) per chip).
  collectives: exactly ONE fused psum per accumulation sweep — the carried
           strategy state ((G, Σp, Σppᵀ) for ``TwoPassExact``, SX for
           ``OnePassSketched``) psums as one tuple, which
           lowers to a single all-reduce — and one all_gather pair (values +
           indices, each (shards, 2, m) with m = #directions) for the
           cross-shard running-extreme hull reduction. Nothing else crosses
           the ICI; leverage scores stay row-sharded until the final
           multi-process-safe ``host_gather``.

The engine drives the same ``repro.core.scoring`` pass strategies as the
single-host engine: ``TwoPassExact`` (the pass1/pass2 pair below, with an
optional x64-gated f64 Gram carry), and ``OnePassSketched`` — ONE fused
sweep (``make_sharded_onepass_fn``) that accumulates the row CountSketch
and the running hull extremes and emits the sketch-projected z rows, so
every data row is featurized exactly once per score call.

Between the sweeps the engine runs the same tiny host algebra as the
single-host path (f64 eigh of the psum'd Gram, moment-derived or upfront
direction net), which is what makes the two engines agree to f32
accumulation noise (~1e-7) on identical inputs regardless of mesh shape or
chunk size.

``distributed_build_coreset`` drives the engine end-to-end and returns the
same ``CoresetResult`` contract as ``coreset.build_coreset``.

The same Gram-psum pattern powers the LM-pipeline coreset stage
(`repro.data.pipeline.CoresetSelector`) with model-embedding features — pass
``mesh=`` to its constructor to route selection through this engine.
"""
from __future__ import annotations

import itertools
import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hull import stable_first_unique
from repro.core.scoring import (
    DEFAULT_CHUNK,
    SCORE_METHODS,
    OnePassSketched,
    ScoringResult,
    TwoPassExact,
    TwoPassSketched,
    _mctm_featurize,
    _z_leverage_jit,
    directions_from_moments,
    finalize_scoring,
    gram_projection,
    hull_chunk_extremes,
    leverage_chunk,
    pass1_update,
    projection_from_gram,
    resolve_strategy,
    sketch_plan,
    upfront_directions,
)
from repro.kernels.gram.ops import gram_matrix
from repro.kernels.sweep.ops import fused_sweep_update
from repro.utils.compat import shard_map

__all__ = [
    "distributed_gram",
    "distributed_leverage",
    "distributed_direction_argmax",
    "distributed_coreset_scores",
    "distributed_scoring_stats",
    "DistributedScoringEngine",
    "distributed_build_coreset",
    "make_sharded_pass_fns",
    "make_sharded_onepass_fn",
    "make_segmented_pass_fns",
    "make_segmented_onepass_fn",
    "host_gather",
    "kv_allreduce",
    "shard_layout",
]


def _axis_tuple(axis) -> tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def _num_shards(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def shard_layout(mesh: Mesh, axis, n: int, chunk_size: int | None):
    """(chunk, chunks_per_shard, n_pad) for n rows chunk-scanned over a mesh.

    The one row-layout rule every sharded chunk driver in the repo follows —
    the scoring engine's shard_map scan bodies and the fit layer's streamed
    evaluator (``core.mctm_fit``) pad/slice with exactly this geometry, so
    arrays staged for one are directly consumable by the other.
    """
    axes = _axis_tuple(axis)
    shards = _num_shards(mesh, axes)
    per_needed = -(-n // shards)
    chunk = int(chunk_size) if chunk_size else per_needed
    chunk = max(min(chunk, per_needed), 1)
    cps = -(-per_needed // chunk)
    return chunk, cps, cps * chunk * shards


def _spec_el(axes: tuple[str, ...]):
    """PartitionSpec element for the row dimension (one axis or a tuple)."""
    return axes if len(axes) > 1 else axes[0]


# monotone per-process call counter: host_gather is SPMD (every process calls
# it in the same order), so the counter names a unique KV namespace + barrier
# per gather that all processes agree on
_KV_GATHER_SEQ = itertools.count()
_KV_ALLREDUCE_SEQ = itertools.count()
_KV_TIMEOUT_MS = 120_000


def _kv_timeout_ms() -> int:
    """KV-store barrier/get deadline — the ft config's ``kv_timeout_ms``.

    This doubles as the peer-death detector for host-level data parallelism:
    when a peer dies mid-step, the survivor's next barrier times out with a
    RuntimeError that ``ft.supervisor.RunSupervisor`` treats as retryable,
    triggering re-planning onto the surviving devices.
    """
    from repro.ft.config import get_ft_config

    return int(get_ft_config().kv_timeout_ms)


def _kv_store_gather(x) -> np.ndarray:
    """Cross-process gather over the distributed runtime's key-value store.

    The CPU backend cannot execute multi-process computations (so
    ``process_allgather`` — a jit under the hood — fails there); exchanging
    the addressable shard bytes host-side through the coordinator's KV store
    covers the gap. Collective: every participating process must call
    ``host_gather`` in the same order.
    """
    import pickle

    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        raise RuntimeError(
            "host_gather: array is not fully addressable but jax.distributed "
            "was never initialized"
        )
    seq = next(_KV_GATHER_SEQ)
    pid = jax.process_index()
    shards = [
        (
            tuple(s.indices(dim)[:2] for s, dim in zip(shard.index, x.shape)),
            np.asarray(shard.data),
        )
        for shard in x.addressable_shards
    ]
    key = f"repro/host_gather/{seq}/{pid}"
    client.key_value_set_bytes(key, pickle.dumps(shards))
    timeout = _kv_timeout_ms()
    client.wait_at_barrier(f"repro_host_gather_{seq}", timeout)
    out = np.zeros(x.shape, x.dtype)
    for p in range(jax.process_count()):
        blob = client.blocking_key_value_get_bytes(
            f"repro/host_gather/{seq}/{p}", timeout
        )
        for bounds, data in pickle.loads(blob):
            out[tuple(slice(a, b) for a, b in bounds)] = data
    # second barrier before deleting our key: every process has read it
    client.wait_at_barrier(f"repro_host_gather_done_{seq}", timeout)
    client.key_value_delete(key)
    return out


def host_gather(x) -> np.ndarray:
    """Multi-process-safe device→host gather.

    Single-process (tests, fake-device meshes): plain ``np.asarray``. Under
    multi-process jax, row-sharded outputs go through
    ``multihost_utils.process_allgather`` and replicated outputs are read
    from a local shard — no path ever touches non-addressable device memory.
    On backends that cannot run multi-process computations (CPU), the gather
    falls back to a host-side shard exchange through the distributed
    runtime's KV store (``_kv_store_gather``).
    """
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    if getattr(x, "is_fully_replicated", False):
        return np.asarray(x.addressable_shards[0].data)
    from jax.experimental import multihost_utils

    try:
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    except Exception as e:
        # fall back ONLY for the known CPU-backend gap ("Multiprocess
        # computations aren't implemented on the CPU backend"); any other
        # failure is a real error and must stay loud
        if jax.default_backend() != "cpu" or (
            "multiprocess computations" not in str(e).lower()
        ):
            raise
        return _kv_store_gather(x)


def kv_allreduce(tree, timeout_ms: int | None = None):
    """Sum-allreduce a pytree of host arrays across jax processes via the
    coordinator's KV store.

    The backbone of CPU-backend-safe host-level data parallelism: each
    process computes local gradients with a plain local jit and exchanges
    them here (the CPU backend cannot run cross-process jit collectives).
    Collective — every process must call in the same order. Single-process:
    identity. A dead peer surfaces as a barrier timeout (RuntimeError after
    ``timeout_ms``, default the ft config's ``kv_timeout_ms``) — the
    supervisor's retryable signal for re-planning onto the survivors.
    """
    import pickle

    from jax._src import distributed

    if jax.process_count() == 1:
        return tree
    client = distributed.global_state.client
    if client is None:
        raise RuntimeError("kv_allreduce: jax.distributed was never initialized")
    timeout = int(timeout_ms) if timeout_ms is not None else _kv_timeout_ms()
    seq = next(_KV_ALLREDUCE_SEQ)
    pid = jax.process_index()
    leaves, treedef = jax.tree.flatten(tree)
    host = [np.asarray(leaf) for leaf in leaves]
    key = f"repro/allreduce/{seq}/{pid}"
    client.key_value_set_bytes(key, pickle.dumps(host))
    client.wait_at_barrier(f"repro_allreduce_{seq}", timeout)
    out = [np.zeros_like(h) for h in host]
    for p in range(jax.process_count()):
        blob = client.blocking_key_value_get_bytes(f"repro/allreduce/{seq}/{p}", timeout)
        for acc, arr in zip(out, pickle.loads(blob)):
            acc += arr
    client.wait_at_barrier(f"repro_allreduce_done_{seq}", timeout)
    client.key_value_delete(key)
    return jax.tree.unflatten(treedef, out)


def distributed_gram(X: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """G = XᵀX with X row-sharded over `axis`; result replicated."""

    def shard_fn(xs):
        return jax.lax.psum(gram_matrix(xs), axis)

    spec_in = P(axis, None)
    spec_out = P(None, None)
    fn = shard_map(shard_fn, mesh=mesh, in_specs=(spec_in,), out_specs=spec_out)
    return fn(X)


def distributed_leverage(X: jax.Array, mesh: Mesh, axis: str = "data") -> jax.Array:
    """Leverage scores with X row-sharded: one psum + local projections."""

    def shard_fn(xs):
        G = jax.lax.psum(gram_matrix(xs), axis)
        V, inv = gram_projection(G)
        return jnp.sum(jnp.square(xs @ V) * inv, axis=1)

    fn = shard_map(
        shard_fn, mesh=mesh, in_specs=(P(axis, None),), out_specs=P(axis)
    )
    return fn(X)


def distributed_scoring_stats(
    X: jax.Array, P_pts: jax.Array, mesh: Mesh, axis: str = "data"
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pass-1 sufficient statistics of the scoring engine, one psum each.

    Returns (G = XᵀX, Σp, Σppᵀ) replicated — everything needed to build the
    leverage projection and the hull direction net without gathering data.
    """

    def shard_fn(xs, ps):
        G = jax.lax.psum(gram_matrix(xs), axis)
        s1 = jax.lax.psum(jnp.sum(ps, axis=0), axis)
        s2 = jax.lax.psum(ps.T @ ps, axis)
        return G, s1, s2

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=(P(None, None), P(None), P(None, None)),
    )
    return fn(X, P_pts)


def distributed_direction_argmax(
    P_pts: jax.Array, dirs: jax.Array, mesh: Mesh, axis: str = "data"
) -> jax.Array:
    """Global argmax_i ⟨p_i, v⟩ per direction, points row-sharded over `axis`.

    Returns global row indices, shape (m,). Implemented as a per-shard argmax
    followed by a cross-shard max over (score, global_index) pairs — the same
    running-extreme reduction the chunked engine's pass 2 performs over
    chunks, here over shards.

    Handles ragged inputs: when ``n % shards != 0`` the rows are padded to a
    shard multiple and the pad rows' scores are masked to −inf, so they can
    never win the argmax and every returned index is a real row. Ties break
    toward the lowest global row index (matching dense ``jnp.argmax``).
    """
    n = int(P_pts.shape[0])
    if n == 0:
        raise ValueError(
            "distributed_direction_argmax: empty input (every shard would be "
            "empty and the per-direction argmax is undefined)"
        )
    shards = mesh.shape[axis]
    per = -(-n // shards)  # ceil → padded rows per shard
    n_pad = per * shards
    if n_pad > n:
        pad = jnp.zeros((n_pad - n, P_pts.shape[1]), P_pts.dtype)
        P_pts = jnp.concatenate([P_pts, pad], axis=0)
    mask = jnp.arange(n_pad) < n

    def shard_fn(ps, ms, vs):
        scores = ps @ vs.T  # (per, m)
        scores = jnp.where(ms[:, 0][:, None], scores, -jnp.inf)
        local_best = jnp.argmax(scores, axis=0)  # (m,)
        local_score = jnp.take_along_axis(scores, local_best[None, :], axis=0)[0]
        shard_id = jax.lax.axis_index(axis)
        global_idx = shard_id * per + local_best
        all_scores = jax.lax.all_gather(local_score, axis)  # (shards, m)
        all_idx = jax.lax.all_gather(global_idx, axis)
        win = jnp.argmax(all_scores, axis=0)  # (m,) first shard wins ties
        return jnp.take_along_axis(all_idx, win[None, :], axis=0)[0]

    fn = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(None, None)),
        out_specs=P(None),
        check_vma=False,  # all_gather+argmax makes the output replicated
    )
    return fn(P_pts, mask[:, None], dirs)


def distributed_coreset_scores(
    X: jax.Array, mesh: Mesh, axis: str = "data"
) -> jax.Array:
    """s_i = u_i + 1/n, computed fully sharded (the Algorithm-1 score step)."""
    n = X.shape[0]
    u = distributed_leverage(X, mesh, axis)
    return u + 1.0 / n


# ---------------------------------------------------------------------------
# DistributedScoringEngine — chunked pass-1/pass-2 inside the shard_map body
# ---------------------------------------------------------------------------


def _shard_index_fn(axes: tuple[str, ...], sizes):
    """Row-major linear shard index over (possibly multiple) mesh axes."""
    idx = jax.lax.axis_index(axes[0])
    for a, s in zip(axes[1:], sizes[1:]):
        idx = idx * s + jax.lax.axis_index(a)
    return idx


# -- running-extreme hull reduction, shared by the two-pass pass-2 body and
#    the one-pass body (the device-side analogue of scoring.RunningExtremes)


def _extremes_init(m: int):
    return (
        jnp.full((m,), -jnp.inf, jnp.float32),
        jnp.zeros((m,), jnp.int32),
        jnp.full((m,), jnp.inf, jnp.float32),
        jnp.zeros((m,), jnp.int32),
    )


def _extremes_fold(ext, block, row_offset):
    """Fold one chunk's block-local directional extremes into the running
    carry.

    Strict comparisons keep the first-occurrence (lowest-row) tie-break,
    matching the single-host running extremes. Indices are cast to int32 so
    the scan carry dtype is stable regardless of x64 mode (the engines guard
    against n·r overflowing int32 up front).
    """
    bmax, imax, bmin, imin = ext
    vmax, lmax, vmin, lmin = block
    gmax = (row_offset + lmax).astype(jnp.int32)
    gmin = (row_offset + lmin).astype(jnp.int32)
    upd = vmax > bmax
    bmax, imax = jnp.where(upd, vmax, bmax), jnp.where(upd, gmax, imax)
    upd = vmin < bmin
    bmin, imin = jnp.where(upd, vmin, bmin), jnp.where(upd, gmin, imin)
    return bmax, imax, bmin, imin


def _extremes_step(ext, Pr, dirs, pm, row_offset):
    """``_extremes_fold`` over the standalone extremes kernel — the two-pass
    scan bodies' step (the one-pass bodies fold the fused sweep's block)."""
    return _extremes_fold(ext, hull_chunk_extremes(Pr, dirs, pm), row_offset)


def _extremes_cross_shard(ext, axis_name):
    """Cross-shard running-extreme reduction: ONE all_gather pair (values +
    indices), then a replicated argmax; lowest shard wins ties. Returns the
    per-direction global (argmax, argmin) row ids."""
    bmax, imax, bmin, imin = ext
    allv = jax.lax.all_gather(jnp.stack([bmax, -bmin]), axis_name)
    alli = jax.lax.all_gather(jnp.stack([imax, imin]), axis_name)
    win = jnp.argmax(allv, axis=0)  # (2, m)
    hull_idx = jnp.take_along_axis(alli, win[None], axis=0)[0]
    return hull_idx[0], hull_idx[1]


def make_sharded_pass_fns(
    featurize: Callable,
    mesh: Mesh,
    axes: tuple[str, ...],
    *,
    chunk: int,
    chunks_per_shard: int,
    rows_per_point: int,
    hull: bool,
    D: int,
    p: int,
    gram_dtype: str = "float32",
):
    """Build the (pass1, pass2) shard_map callables of the sharded engine.

    Shapes per shard: inputs are (per, …) slices with per = chunks_per_shard
    · chunk; the body reshapes them into (chunks_per_shard, chunk, …) and
    ``lax.scan``s the single-host per-chunk updates over them. Exposed
    separately from the engine so the pod dry-run can lower the exact same
    computation from ShapeDtypeStructs (``launch.dryrun_coreset`` variant
    ``engine``).

    pass1(Y, sw_masked, mask) -> (G, Σp, Σppᵀ) replicated — one fused psum.
    pass2(Y, sw_masked, mask, V, inv[, dirs]) -> row-sharded leverage, plus
    (when ``hull``) the per-direction global argmax/argmin row indices from
    the cross-shard running-extreme reduction (one all_gather pair).

    ``gram_dtype="float64"`` carries (and psums) the Gram in f64 — the
    sharded realization of ``TwoPassExact(gram_dtype="float64")`` — which
    requires jax x64 mode (the single-host engine accumulates host-side
    instead and needs no flag).
    """
    r = rows_per_point
    cps = chunks_per_shard
    per = cps * chunk
    sizes = [mesh.shape[a] for a in axes]
    axis_name = axes if len(axes) > 1 else axes[0]
    row_spec = _spec_el(axes)
    f64 = gram_dtype == "float64"

    def _shard_index():
        return _shard_index_fn(axes, sizes)

    def _chunked(a):
        return a.reshape((cps, chunk) + a.shape[1:])

    def pass1_body(ys, swm, mask):
        def step(carry, xs):
            yc, swc, mc = xs
            X, Pr = featurize(yc)
            if hull:
                # zero pad rows out of the moments: Σp / Σppᵀ must cover
                # exactly the n·r real derivative rows
                Pr = Pr * jnp.repeat(mc, r)[:, None]
            else:
                Pr = None
            return (
                pass1_update(
                    carry[0], carry[1], carry[2], X, Pr, swc, gram_dtype=gram_dtype
                ),
                None,
            )

        init = (
            jnp.zeros((D, D), jnp.float64 if f64 else jnp.float32),
            jnp.zeros((p,), jnp.float32),
            jnp.zeros((p, p), jnp.float32),
        )
        carry, _ = jax.lax.scan(
            step, init, (_chunked(ys), _chunked(swm), _chunked(mask))
        )
        # ONE collective: the tuple psum lowers to a single fused all-reduce
        return jax.lax.psum(carry, axis_name)

    pass1 = shard_map(
        pass1_body,
        mesh=mesh,
        in_specs=(P(row_spec, None), P(row_spec), P(row_spec)),
        out_specs=(P(None, None), P(None), P(None, None)),
        check_vma=False,
    )

    def pass2_hull_body(ys, swm, mask, V, inv, dirs):
        base = _shard_index() * per

        def step(carry, xs):
            ci, yc, swc, mc = xs
            X, Pr = featurize(yc)
            u = leverage_chunk(X, swc, V, inv)
            pm = jnp.repeat(mc, r) > 0
            carry = _extremes_step(carry, Pr, dirs, pm, (base + ci * chunk) * r)
            return carry, u

        ext, u = jax.lax.scan(
            step,
            _extremes_init(dirs.shape[0]),
            (jnp.arange(cps), _chunked(ys), _chunked(swm), _chunked(mask)),
        )
        # the distributed analogue of the host-side chunk loop in
        # ScoringEngine._drive
        gimax, gimin = _extremes_cross_shard(ext, axis_name)
        return u.reshape(per), gimax, gimin

    def pass2_body(ys, swm, V, inv):
        def step(_, xs):
            yc, swc = xs
            X, _ = featurize(yc)
            return None, leverage_chunk(X, swc, V, inv)

        _, u = jax.lax.scan(step, None, (_chunked(ys), _chunked(swm)))
        return u.reshape(per)

    if hull:
        pass2 = shard_map(
            pass2_hull_body,
            mesh=mesh,
            in_specs=(
                P(row_spec, None),
                P(row_spec),
                P(row_spec),
                P(None, None),
                P(None),
                P(None, None),
            ),
            out_specs=(P(row_spec), P(None), P(None)),
            check_vma=False,
        )
    else:
        pass2 = shard_map(
            pass2_body,
            mesh=mesh,
            in_specs=(P(row_spec, None), P(row_spec), P(None, None), P(None)),
            out_specs=P(row_spec),
            check_vma=False,
        )
    return pass1, pass2


def make_sharded_onepass_fn(
    featurize: Callable,
    mesh: Mesh,
    axes: tuple[str, ...],
    *,
    chunk: int,
    chunks_per_shard: int,
    rows_per_point: int,
    hull: bool,
    D: int,
    q: int | None,
    sketch_size: int,
):
    """The sharded ``OnePassSketched`` sweep — ONE shard_map callable.

    Each shard scans its local chunks exactly once, accumulating the
    strategy's carried state (the row CountSketch SX — it joins the one
    fused psum, exactly like the two-pass (G, Σp, Σppᵀ); the one-pass net is
    fixed upfront so no hull moments are carried) while tracking the running
    directional hull extremes and emitting the sketch-projected rows
    z = (√w·X)Ω. No second data sweep exists: leverage is read off the
    row-sharded z at finalize.

    fn(Y, sw_masked, mask, rows, signs, *extras) with ``rows``/``signs`` the
    row-sharded global CountSketch plan, extras = (Ω,) when ``q`` plus
    (dirs,) when ``hull``. Returns (z row-sharded, SX replicated
    [, global argmax/argmin row ids]).
    """
    r = rows_per_point
    cps = chunks_per_shard
    per = cps * chunk
    sizes = [mesh.shape[a] for a in axes]
    axis_name = axes if len(axes) > 1 else axes[0]
    row_spec = _spec_el(axes)
    width = q if q else D

    def _chunked(a):
        return a.reshape((cps, chunk) + a.shape[1:])

    def body(ys, swm, mask, rows, signs, *extra):
        omega = extra[0] if q else None
        dirs = extra[-1] if hull else None
        m = dirs.shape[0] if hull else 0
        base = _shard_index_fn(axes, sizes) * per

        def step(carry, xs):
            SX, ext = carry
            ci, yc, swc, mc, rc, sc = xs
            X, Pr = featurize(yc)
            # ONE fused op per chunk (kernels.sweep): sketch + z + extremes
            SX, z, extb, _ = fused_sweep_update(
                SX, X, Pr if hull else None, swc, rc, sc,
                dirs=dirs, omega=omega, mask=mc if hull else None,
            )
            if hull:
                ext = _extremes_fold(ext, extb, (base + ci * chunk) * r)
            return (SX, ext), z

        init = (jnp.zeros((sketch_size, D), jnp.float32), _extremes_init(m))
        (SX, ext), z = jax.lax.scan(
            step,
            init,
            (
                jnp.arange(cps),
                _chunked(ys),
                _chunked(swm),
                _chunked(mask),
                _chunked(rows),
                _chunked(signs),
            ),
        )
        # ONE collective for the strategy state, same as the two-pass pass 1
        SX = jax.lax.psum(SX, axis_name)
        outs = (z.reshape(per, width), SX)
        if hull:
            outs = outs + _extremes_cross_shard(ext, axis_name)
        return outs

    row = P(row_spec)
    in_specs = (P(row_spec, None), row, row, row, row)
    if q:
        in_specs = in_specs + (P(None, None),)
    if hull:
        in_specs = in_specs + (P(None, None),)
    out_specs = (P(row_spec, None), P(None, None))
    if hull:
        out_specs = out_specs + (P(None), P(None))
    return shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )


def make_segmented_pass_fns(
    featurize: Callable,
    mesh: Mesh,
    axes: tuple[str, ...],
    *,
    chunk: int,
    seg_chunks: int,
    total_chunks: int,
    rows_per_point: int,
    hull: bool,
    D: int,
    p: int,
    gram_dtype: str = "float32",
):
    """Segmented (resumable) variants of ``make_sharded_pass_fns``.

    Each call scans only ``seg_chunks`` of the ``total_chunks`` per-shard
    chunks and carries the PER-SHARD partial statistics in and out (leading
    shards axis, row-sharded) instead of psumming them — the cross-shard
    reduction happens exactly once, host-side, after the last segment. That
    preserves the per-shard accumulation order bit-for-bit across any
    interrupt/resume boundary, which is what makes the segmented sweeps
    (``DistributedScoringEngine.score(sweep_ckpt=...)``) resume
    bit-identically to their uninterrupted runs.

    pass1_seg(Y_seg, swm_seg, mask_seg, G, s1, s2) -> updated per-shard
    (shards, D, D)/(shards, p)/(shards, p, p) carries.
    pass2_seg: hull variant (…, V, inv, dirs, bmax, imax, bmin, imin, c0) ->
    (u_seg row-sharded, updated per-shard extremes); plain variant
    (…, V, inv) -> u_seg. ``c0`` is the replicated starting chunk index of
    the segment, so global hull row offsets stay exact mid-sweep.
    """
    r = rows_per_point
    per_full = total_chunks * chunk
    sizes = [mesh.shape[a] for a in axes]
    row_spec = _spec_el(axes)

    def _chunked(a):
        return a.reshape((seg_chunks, chunk) + a.shape[1:])

    def pass1_body(ys, swm, mask, G, s1, s2):
        def step(carry, xs):
            yc, swc, mc = xs
            X, Pr = featurize(yc)
            if hull:
                Pr = Pr * jnp.repeat(mc, r)[:, None]
            else:
                Pr = None
            return (
                pass1_update(
                    carry[0], carry[1], carry[2], X, Pr, swc, gram_dtype=gram_dtype
                ),
                None,
            )

        carry, _ = jax.lax.scan(
            step, (G[0], s1[0], s2[0]), (_chunked(ys), _chunked(swm), _chunked(mask))
        )
        # NO psum — the per-shard partials go back to the host checkpoint
        return carry[0][None], carry[1][None], carry[2][None]

    row = P(row_spec)
    pass1 = shard_map(
        pass1_body,
        mesh=mesh,
        in_specs=(
            P(row_spec, None),
            row,
            row,
            P(row_spec, None, None),
            P(row_spec, None),
            P(row_spec, None, None),
        ),
        out_specs=(
            P(row_spec, None, None),
            P(row_spec, None),
            P(row_spec, None, None),
        ),
        check_vma=False,
    )

    def pass2_hull_body(ys, swm, mask, V, inv, dirs, bmax, imax, bmin, imin, c0):
        base = _shard_index_fn(axes, sizes) * per_full

        def step(carry, xs):
            ci, yc, swc, mc = xs
            X, Pr = featurize(yc)
            u = leverage_chunk(X, swc, V, inv)
            pm = jnp.repeat(mc, r) > 0
            carry = _extremes_step(carry, Pr, dirs, pm, (base + (c0 + ci) * chunk) * r)
            return carry, u

        ext, u = jax.lax.scan(
            step,
            (bmax[0], imax[0], bmin[0], imin[0]),
            (jnp.arange(seg_chunks), _chunked(ys), _chunked(swm), _chunked(mask)),
        )
        return (u.reshape(seg_chunks * chunk),) + tuple(e[None] for e in ext)

    def pass2_body(ys, swm, V, inv):
        def step(_, xs):
            yc, swc = xs
            X, _ = featurize(yc)
            return None, leverage_chunk(X, swc, V, inv)

        _, u = jax.lax.scan(step, None, (_chunked(ys), _chunked(swm)))
        return u.reshape(seg_chunks * chunk)

    if hull:
        pass2 = shard_map(
            pass2_hull_body,
            mesh=mesh,
            in_specs=(
                P(row_spec, None),
                row,
                row,
                P(None, None),
                P(None),
                P(None, None),
                P(row_spec, None),
                P(row_spec, None),
                P(row_spec, None),
                P(row_spec, None),
                P(),
            ),
            out_specs=(
                row,
                P(row_spec, None),
                P(row_spec, None),
                P(row_spec, None),
                P(row_spec, None),
            ),
            check_vma=False,
        )
    else:
        pass2 = shard_map(
            pass2_body,
            mesh=mesh,
            in_specs=(P(row_spec, None), row, P(None, None), P(None)),
            out_specs=row,
            check_vma=False,
        )
    return pass1, pass2


def make_segmented_onepass_fn(
    featurize: Callable,
    mesh: Mesh,
    axes: tuple[str, ...],
    *,
    chunk: int,
    seg_chunks: int,
    total_chunks: int,
    rows_per_point: int,
    hull: bool,
    D: int,
    q: int | None,
    sketch_size: int,
):
    """Segmented (resumable) ``make_sharded_onepass_fn`` — see
    ``make_segmented_pass_fns`` for the per-shard carry contract. One call
    scans ``seg_chunks`` chunks, carrying the PER-SHARD CountSketch (and
    hull extremes) in and out with no psum, and emits that segment's
    sketch-projected z rows.

    fn(Y_seg, swm_seg, mask_seg, rows_seg, signs_seg, SX, c0, *extra) with
    extra = (Ω,) when ``q`` plus (bmax, imax, bmin, imin, dirs) when
    ``hull``; returns (z_seg row-sharded, SX' per-shard[, extremes']).
    """
    r = rows_per_point
    per_full = total_chunks * chunk
    sizes = [mesh.shape[a] for a in axes]
    row_spec = _spec_el(axes)
    width = q if q else D

    def _chunked(a):
        return a.reshape((seg_chunks, chunk) + a.shape[1:])

    def body(ys, swm, mask, rows, signs, SX, c0, *extra):
        i = 0
        omega = None
        if q:
            omega = extra[0]
            i = 1
        if hull:
            bmax, imax, bmin, imin, dirs = extra[i : i + 5]
        base = _shard_index_fn(axes, sizes) * per_full

        def step(carry, xs):
            SXc, ext = carry
            ci, yc, swc, mc, rc, sc = xs
            X, Pr = featurize(yc)
            # same fused op as the non-segmented sweep — the per-shard carry
            # layout (and so the segment checkpoints) is unchanged
            SXc, z, extb, _ = fused_sweep_update(
                SXc, X, Pr if hull else None, swc, rc, sc,
                dirs=dirs if hull else None, omega=omega,
                mask=mc if hull else None,
            )
            if hull:
                ext = _extremes_fold(
                    ext, extb, (base + (c0 + ci) * chunk) * r
                )
            return (SXc, ext), z

        ext0 = (bmax[0], imax[0], bmin[0], imin[0]) if hull else ()
        (SXc, ext), z = jax.lax.scan(
            step,
            (SX[0], ext0),
            (
                jnp.arange(seg_chunks),
                _chunked(ys),
                _chunked(swm),
                _chunked(mask),
                _chunked(rows),
                _chunked(signs),
            ),
        )
        outs = (z.reshape(seg_chunks * chunk, width), SXc[None])
        if hull:
            outs = outs + tuple(e[None] for e in ext)
        return outs

    row = P(row_spec)
    in_specs = (P(row_spec, None), row, row, row, row, P(row_spec, None, None), P())
    if q:
        in_specs = in_specs + (P(None, None),)
    if hull:
        in_specs = in_specs + (P(row_spec, None),) * 4 + (P(None, None),)
    out_specs = (P(row_spec, None), P(row_spec, None, None))
    if hull:
        out_specs = out_specs + (P(row_spec, None),) * 4
    return shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )


class DistributedScoringEngine:
    """Sharded + chunked pre-sampling phase of Algorithm 1 (see module doc).

    Same contract as ``scoring.ScoringEngine.score`` — returns an identical
    ``ScoringResult`` — but every data-sized computation runs inside the mesh:
    per-chip memory is O(chunk·J·d) and the only cross-chip traffic is one
    fused pass-1 psum and one pass-2 all_gather pair.

    Parameters mirror ``ScoringEngine``; ``featurize`` must be jax-traceable
    (it runs inside the shard_map scan body). ``axis`` may be one mesh axis
    name or a tuple of names (e.g. ``("pod", "data")`` on a multi-pod mesh).
    ``sketch_size > 0`` (or an explicit ``OnePassSketched`` strategy) routes
    through the fused one-pass sweep — each row featurized exactly once, the
    sketch state joining the single pass-1 psum.
    """

    def __init__(
        self,
        cfg=None,
        scaler=None,
        *,
        mesh: Mesh,
        axis="data",
        featurize: Callable | None = None,
        chunk_size: int | None = DEFAULT_CHUNK,
        rows_per_point: int | None = None,
        hull_oversample: int = 4,
        gram_dtype: str = "float32",
    ):
        if featurize is None:
            if cfg is None or scaler is None:
                raise ValueError("either (cfg, scaler) or featurize is required")
            featurize = _mctm_featurize(cfg, scaler)
            rows_per_point = cfg.J
        self.cfg = cfg
        self.scaler = scaler
        self.featurize = featurize
        self.mesh = mesh
        self.axes = _axis_tuple(axis)
        self.chunk_size = int(chunk_size) if chunk_size else 0
        self.rows_per_point = int(rows_per_point or 1)
        self.hull_oversample = hull_oversample
        self.gram_dtype = gram_dtype
        self._fns: dict = {}  # layout/strategy signature → jitted pass fns

    # --------------------------------------------------------------- helpers

    def _shard_layout(self, n: int) -> tuple[int, int, int]:
        """(chunk, chunks_per_shard, n_pad) for n rows over this mesh."""
        return shard_layout(self.mesh, self.axes, n, self.chunk_size)

    def _feature_shapes(self, chunk: int, hull: bool, width, dtype):
        sds = jax.ShapeDtypeStruct((chunk,) + width, dtype)
        X_s, P_s = jax.eval_shape(self.featurize, sds)
        if hull and P_s is None:
            raise ValueError("hull_k > 0 requires a featurize that returns P rows")
        D = int(X_s.shape[1])
        # without a hull stage s1/s2 stay zero — carry (and psum) scalars,
        # not a (p, p) dead weight the size of the Gram
        p = int(P_s.shape[1]) if (hull and P_s is not None) else 1
        return D, p

    def _pass_fns(self, chunk: int, cps: int, hull: bool, width, dtype, gram_dtype):
        D, p = self._feature_shapes(chunk, hull, width, dtype)
        key = ("two-pass", chunk, cps, hull, D, p, gram_dtype)
        if key not in self._fns:
            p1, p2 = make_sharded_pass_fns(
                self.featurize,
                self.mesh,
                self.axes,
                chunk=chunk,
                chunks_per_shard=cps,
                rows_per_point=self.rows_per_point,
                hull=hull,
                D=D,
                p=p,
                gram_dtype=gram_dtype,
            )
            self._fns[key] = (jax.jit(p1), jax.jit(p2))
        return self._fns[key]

    def _onepass_fn(
        self, chunk: int, cps: int, hull: bool, width, dtype, proj_size, sketch_size
    ):
        D, _ = self._feature_shapes(chunk, hull, width, dtype)
        # same normalization as OnePassSketched.begin: Ω only when it shrinks
        q = proj_size if (proj_size is not None and proj_size < D) else None
        key = ("one-pass", chunk, cps, hull, D, q, sketch_size)
        if key not in self._fns:
            fn = make_sharded_onepass_fn(
                self.featurize,
                self.mesh,
                self.axes,
                chunk=chunk,
                chunks_per_shard=cps,
                rows_per_point=self.rows_per_point,
                hull=hull,
                D=D,
                q=q,
                sketch_size=sketch_size,
            )
            self._fns[key] = (jax.jit(fn), D)
        return self._fns[key]

    def _segment_fns(self, chunk, seg, cps, hull, width, dtype, gram_dtype):
        D, p = self._feature_shapes(chunk, hull, width, dtype)
        key = ("seg-two-pass", chunk, seg, cps, hull, D, p, gram_dtype)
        if key not in self._fns:
            p1, p2 = make_segmented_pass_fns(
                self.featurize,
                self.mesh,
                self.axes,
                chunk=chunk,
                seg_chunks=seg,
                total_chunks=cps,
                rows_per_point=self.rows_per_point,
                hull=hull,
                D=D,
                p=p,
                gram_dtype=gram_dtype,
            )
            self._fns[key] = (jax.jit(p1), jax.jit(p2), D, p)
        return self._fns[key]

    def _segment_onepass_fn(
        self, chunk, seg, cps, hull, width, dtype, proj_size, sketch_size
    ):
        D, _ = self._feature_shapes(chunk, hull, width, dtype)
        q = proj_size if (proj_size is not None and proj_size < D) else None
        key = ("seg-one-pass", chunk, seg, cps, hull, D, q, sketch_size)
        if key not in self._fns:
            fn = make_segmented_onepass_fn(
                self.featurize,
                self.mesh,
                self.axes,
                chunk=chunk,
                seg_chunks=seg,
                total_chunks=cps,
                rows_per_point=self.rows_per_point,
                hull=hull,
                D=D,
                q=q,
                sketch_size=sketch_size,
            )
            self._fns[key] = (jax.jit(fn), D, q)
        return self._fns[key]

    def _score_segmented(
        self, strat, key, Y, weights, method, ridge_reg, hull_k, hull_key,
        sweep_ckpt, resume, hull_dirs=None,
    ):
        """The resumable sweep driver: host-held per-shard partials, atomic
        segment checkpoints, ONE host-side cross-shard reduction at the end.

        The host keeps the full padded data (this path targets robustness,
        not peak scale) and stages one segment's rows at a time; the device
        never holds more than a segment. Checkpoint payloads have fixed
        shapes for a given (n, mesh, chunk) layout — resume requires the
        same layout that wrote the sweep checkpoints.
        """
        from repro.checkpoint.manager import CheckpointManager
        from repro.ft.config import get_ft_config, maybe_inject

        r = self.rows_per_point
        hull = hull_k > 0
        Y = np.asarray(Y)
        n = int(Y.shape[0])
        if n == 0:
            raise ValueError("cannot score an empty dataset")
        chunk, cps, n_pad = self._shard_layout(n)
        shards = _num_shards(self.mesh, self.axes)
        per = cps * chunk
        pad = n_pad - n
        dtype = jax.dtypes.canonicalize_dtype(Y.dtype)
        if pad:
            Y_pad = np.concatenate(
                [Y, np.broadcast_to(Y[:1], (pad,) + Y.shape[1:])], axis=0
            )
        else:
            Y_pad = Y
        Y_pad = np.ascontiguousarray(Y_pad, dtype)
        mask = (np.arange(n_pad) < n).astype(np.float32)
        sw = (
            np.sqrt(np.asarray(weights, np.float32))
            if weights is not None
            else np.ones((n,), np.float32)
        )
        swm = np.concatenate([sw, np.zeros((pad,), np.float32)]) if pad else sw

        root = getattr(sweep_ckpt, "directory", sweep_ckpt)
        every = max(int(get_ft_config().sweep_ckpt_every_chunks), 1)
        mgr1 = CheckpointManager(os.path.join(root, "sweep1"), keep=2)
        mgr2 = CheckpointManager(os.path.join(root, "sweep2"), keep=2)

        def seg_rows(arr, c0, c1):
            # global layout is row-sharded: shard s owns rows [s·per, (s+1)·per);
            # a segment takes each shard's chunks [c0, c1)
            tail = arr.shape[1:]
            a = arr.reshape((shards, per) + tail)[:, c0 * chunk : c1 * chunk]
            return np.ascontiguousarray(
                a.reshape((shards * (c1 - c0) * chunk,) + tail)
            )

        def segments(done):
            c0 = done
            while c0 < cps:
                yield c0, min(c0 + every, cps)
                c0 += every

        if isinstance(strat, OnePassSketched):
            return self._segmented_one_pass(
                strat, key, Y_pad, swm, mask, n, n_pad, chunk, cps, shards,
                method, ridge_reg, hull_k, hull_key, dtype,
                mgr1, seg_rows, segments, maybe_inject, resume,
                hull_dirs=hull_dirs,
            )

        # ------------------------------------------------ two-pass, sweep 1
        f64 = strat.gram_dtype == "float64"
        _, _, D, p = self._segment_fns(
            chunk, min(every, cps), cps, hull, Y_pad.shape[1:], dtype,
            strat.gram_dtype,
        )
        G_h = np.zeros((shards, D, D), np.float64 if f64 else np.float32)
        s1_h = np.zeros((shards, p), np.float32)
        s2_h = np.zeros((shards, p, p), np.float32)
        done1 = 0

        def payload1():
            return {
                "chunks": np.asarray(done1, np.int64),
                "G": G_h,
                "s1": s1_h,
                "s2": s2_h,
            }

        if resume and mgr1.latest_step() is not None:
            got = mgr1.restore(payload1())
            done1 = int(got["chunks"])
            G_h, s1_h, s2_h = (
                np.asarray(got["G"]),
                np.asarray(got["s1"]),
                np.asarray(got["s2"]),
            )
        for c0, c1 in segments(done1):
            p1, _, _, _ = self._segment_fns(
                chunk, c1 - c0, cps, hull, Y_pad.shape[1:], dtype,
                strat.gram_dtype,
            )
            G_d, s1_d, s2_d = p1(
                self._shard_put(seg_rows(Y_pad, c0, c1)),
                self._shard_put(seg_rows(swm, c0, c1)),
                self._shard_put(seg_rows(mask, c0, c1)),
                self._shard_put(G_h),
                self._shard_put(s1_h),
                self._shard_put(s2_h),
            )
            G_h, s1_h, s2_h = (
                host_gather(G_d),
                host_gather(s1_d),
                host_gather(s2_d),
            )
            done1 = c1
            mgr1.save(done1, payload1())
            maybe_inject("scoring", done1)

        # one host-side cross-shard reduction (deterministic order — the
        # resumed and uninterrupted runs sum identical per-shard partials)
        G_tot = G_h.sum(axis=0)
        V, inv = projection_from_gram(G_tot, method, ridge_reg)
        dirs = None
        if hull:
            if hull_dirs is not None:
                dirs = np.asarray(hull_dirs, np.float32)
            else:
                dirs = np.asarray(
                    directions_from_moments(
                        hull_key, s1_h.sum(axis=0), s2_h.sum(axis=0), n * r,
                        hull_k, self.hull_oversample,
                    )
                )

        # ------------------------------------------------ two-pass, sweep 2
        m = int(dirs.shape[0]) if hull else 0
        u_h = np.zeros((shards, per), np.float32)
        bmax_h = np.full((shards, m), -np.inf, np.float32)
        imax_h = np.zeros((shards, m), np.int32)
        bmin_h = np.full((shards, m), np.inf, np.float32)
        imin_h = np.zeros((shards, m), np.int32)
        done2 = 0

        def payload2():
            d = {"chunks": np.asarray(done2, np.int64), "u": u_h}
            if hull:
                d.update(bmax=bmax_h, imax=imax_h, bmin=bmin_h, imin=imin_h)
            return d

        if resume and mgr2.latest_step() is not None:
            got = mgr2.restore(payload2())
            done2 = int(got["chunks"])
            u_h = np.asarray(got["u"])
            if hull:
                bmax_h, imax_h = np.asarray(got["bmax"]), np.asarray(got["imax"])
                bmin_h, imin_h = np.asarray(got["bmin"]), np.asarray(got["imin"])
        for c0, c1 in segments(done2):
            _, p2, _, _ = self._segment_fns(
                chunk, c1 - c0, cps, hull, Y_pad.shape[1:], dtype,
                strat.gram_dtype,
            )
            ys = self._shard_put(seg_rows(Y_pad, c0, c1))
            sws = self._shard_put(seg_rows(swm, c0, c1))
            if hull:
                u_seg, bmax_d, imax_d, bmin_d, imin_d = p2(
                    ys, sws, self._shard_put(seg_rows(mask, c0, c1)),
                    jnp.asarray(V), jnp.asarray(inv), jnp.asarray(dirs),
                    self._shard_put(bmax_h), self._shard_put(imax_h),
                    self._shard_put(bmin_h), self._shard_put(imin_h),
                    jnp.asarray(c0, jnp.int32),
                )
                bmax_h, imax_h = host_gather(bmax_d), host_gather(imax_d)
                bmin_h, imin_h = host_gather(bmin_d), host_gather(imin_d)
            else:
                u_seg = p2(ys, sws, jnp.asarray(V), jnp.asarray(inv))
            u_h[:, c0 * chunk : c1 * chunk] = host_gather(u_seg).reshape(
                shards, (c1 - c0) * chunk
            )
            done2 = c1
            mgr2.save(done2, payload2())
            maybe_inject("scoring", cps + done2)

        hull_rows = None
        if hull:
            hull_rows = self._reduce_extremes_host(
                bmax_h, imax_h, bmin_h, imin_h
            )
        u = u_h.reshape(n_pad)[:n]
        return finalize_scoring(n, cps * shards, method, G_tot, u, hull_rows, r)

    def _segmented_one_pass(
        self, strat, key, Y_pad, swm, mask, n, n_pad, chunk, cps, shards,
        method, ridge_reg, hull_k, hull_key, dtype,
        mgr1, seg_rows, segments, maybe_inject, resume, hull_dirs=None,
    ):
        """Segmented one-pass sketched sweep (single data sweep, resumable)."""
        r = self.rows_per_point
        hull = hull_k > 0
        per = cps * chunk
        pad = n_pad - n
        D, _ = self._feature_shapes(chunk, hull, Y_pad.shape[1:], dtype)
        q = (
            strat.proj_size
            if (strat.proj_size is not None and strat.proj_size < D)
            else None
        )
        width = q if q else D
        rows, signs, omega = strat.begin(n, D, key)
        rows = np.asarray(rows)
        signs = np.asarray(signs)
        if pad:
            rows = np.concatenate([rows, np.zeros((pad,), rows.dtype)])
            signs = np.concatenate([signs, np.zeros((pad,), signs.dtype)])
        dirs1 = None
        m = 0
        if hull:
            dirs1 = np.asarray(
                hull_dirs
                if hull_dirs is not None
                else upfront_directions(
                    hull_key, self._p_rows_width(chunk, Y_pad), hull_k,
                    self.hull_oversample,
                ),
                np.float32,
            )
            m = int(dirs1.shape[0])

        SX_h = np.zeros((shards, strat.sketch_size, D), np.float32)
        z_h = np.zeros((shards, per, width), np.float32)
        bmax_h = np.full((shards, m), -np.inf, np.float32)
        imax_h = np.zeros((shards, m), np.int32)
        bmin_h = np.full((shards, m), np.inf, np.float32)
        imin_h = np.zeros((shards, m), np.int32)
        done = 0

        def payload():
            d = {"chunks": np.asarray(done, np.int64), "SX": SX_h, "z": z_h}
            if hull:
                d.update(bmax=bmax_h, imax=imax_h, bmin=bmin_h, imin=imin_h)
            return d

        if resume and mgr1.latest_step() is not None:
            got = mgr1.restore(payload())
            done = int(got["chunks"])
            SX_h, z_h = np.asarray(got["SX"]), np.asarray(got["z"])
            if hull:
                bmax_h, imax_h = np.asarray(got["bmax"]), np.asarray(got["imax"])
                bmin_h, imin_h = np.asarray(got["bmin"]), np.asarray(got["imin"])
        for c0, c1 in segments(done):
            fn, _, _ = self._segment_onepass_fn(
                chunk, c1 - c0, cps, hull, Y_pad.shape[1:], dtype,
                strat.proj_size, strat.sketch_size,
            )
            extras = ()
            if omega is not None:
                extras = extras + (jnp.asarray(omega),)
            if hull:
                extras = extras + (
                    self._shard_put(bmax_h), self._shard_put(imax_h),
                    self._shard_put(bmin_h), self._shard_put(imin_h),
                    jnp.asarray(dirs1),
                )
            outs = fn(
                self._shard_put(seg_rows(Y_pad, c0, c1)),
                self._shard_put(seg_rows(swm, c0, c1)),
                self._shard_put(seg_rows(mask, c0, c1)),
                self._shard_put(seg_rows(rows, c0, c1)),
                self._shard_put(seg_rows(signs, c0, c1)),
                self._shard_put(SX_h),
                jnp.asarray(c0, jnp.int32),
                *extras,
            )
            z_h[:, c0 * chunk : c1 * chunk] = host_gather(outs[0]).reshape(
                shards, (c1 - c0) * chunk, width
            )
            SX_h = host_gather(outs[1])
            if hull:
                bmax_h, imax_h = host_gather(outs[2]), host_gather(outs[3])
                bmin_h, imin_h = host_gather(outs[4]), host_gather(outs[5])
            done = c1
            mgr1.save(done, payload())
            maybe_inject("scoring", done)

        SX_tot = SX_h.sum(axis=0)
        SXp = SX_tot if omega is None else SX_tot @ np.asarray(omega)
        V, inv = projection_from_gram(SXp.T @ SXp, method, ridge_reg)
        z_flat = z_h.reshape(n_pad, width)
        u = np.concatenate(
            [
                np.asarray(_z_leverage_jit(jnp.asarray(z_flat[lo : lo + per]), V, inv))
                for lo in range(0, n_pad, per)
            ]
        )[:n]
        hull_rows = None
        if hull:
            hull_rows = self._reduce_extremes_host(bmax_h, imax_h, bmin_h, imin_h)
        G_host = SX_tot.T @ SX_tot
        return finalize_scoring(n, cps * shards, method, G_host, u, hull_rows, r)

    @staticmethod
    def _reduce_extremes_host(bmax_h, imax_h, bmin_h, imin_h):
        """Host analogue of ``_extremes_cross_shard``: lowest shard wins ties,
        then first-occurrence dedup — matching the in-mesh reduction."""
        m = bmax_h.shape[1]
        cols = np.arange(m)
        gimax = imax_h[np.argmax(bmax_h, axis=0), cols]
        gimin = imin_h[np.argmax(-bmin_h, axis=0), cols]
        return stable_first_unique(
            np.concatenate([gimax, gimin]).astype(np.int64)
        )

    def _shard_put(self, x, row_sharded: bool = True):
        spec = (
            P(_spec_el(self.axes), *([None] * (x.ndim - 1)))
            if row_sharded
            else P(*([None] * x.ndim))
        )
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # ---------------------------------------------------------------- public

    def stage_rows(self, blocks, n: int, width: int, dtype=jnp.float32):
        """Zero-copy sharded staging of n feature rows from host blocks.

        ``blocks`` iterates host arrays of shape (b_i, width) with Σb_i = n
        (any block sizes; O(chunk) each keeps host RSS at O(chunk·width)).
        Each block is split at shard boundaries and device_put straight to
        its target device(s); the padded row-sharded (n_pad, width) global
        array — the exact layout ``score`` uses — is assembled with
        ``make_array_from_single_device_arrays`` without ever materializing
        the (n, width) matrix on the host. Pass the result to
        ``score(..., n_valid=n)``.

        Single-process meshes only (every device must be addressable).
        """
        _, _, n_pad = self._shard_layout(n)
        sharding = NamedSharding(self.mesh, P(_spec_el(self.axes), None))
        dmap = sharding.devices_indices_map((n_pad, width))
        # devices grouped by their row range (replicated non-data axes mean
        # several devices can carry the same rows)
        by_range: dict[tuple[int, int], list] = {}
        for dev, idx in dmap.items():
            lo, hi, _ = idx[0].indices(n_pad)
            by_range.setdefault((lo, hi), []).append(dev)
        pieces: dict = {dev: [] for dev in dmap}
        off = 0
        first_row = None
        for block in blocks:
            block = np.asarray(block, dtype)
            if first_row is None and block.shape[0]:
                first_row = block[:1].copy()
            hi = off + block.shape[0]
            for (rlo, rhi), devs in by_range.items():
                a, b = max(off, rlo), min(hi, rhi)
                if a < b:
                    piece = block[a - off : b - off]
                    for dev in devs:
                        pieces[dev].append(jax.device_put(piece, dev))
            off = hi
        if off != n or first_row is None:
            raise ValueError(f"stage_rows: blocks carried {off} rows, expected {n}")
        shard_arrays = []
        for dev, idx in dmap.items():
            rlo, rhi, _ = idx[0].indices(n_pad)
            have = sum(int(p.shape[0]) for p in pieces[dev])
            want = rhi - rlo
            if have < want:
                # pad with copies of a REAL row, matching score()'s own
                # padding: zeros could featurize to NaN (e.g. log features)
                # and NaN·0 masking would poison the psum'd statistics
                pieces[dev].append(
                    jax.device_put(
                        np.broadcast_to(first_row, (want - have, width)).copy(), dev
                    )
                )
            ps = pieces[dev]
            shard_arrays.append(ps[0] if len(ps) == 1 else jnp.concatenate(ps))
        return jax.make_array_from_single_device_arrays(
            (n_pad, width), sharding, shard_arrays
        )

    def score(
        self,
        Y,
        *,
        method: str = "l2-hull",
        weights=None,
        hull_k: int = 0,
        hull_key: jax.Array | None = None,
        ridge_reg: float = 1.0,
        sketch_size: int = 0,
        key: jax.Array | None = None,
        strategy=None,
        gram_dtype: str | None = None,
        hull_dirs=None,
        n_valid: int | None = None,
        sweep_ckpt=None,
        resume: bool = False,
    ) -> ScoringResult:
        """Score all n points on the mesh; same semantics (and the same pass
        strategies) as the single-host ``ScoringEngine.score``.

        ``hull_dirs`` (m, p) overrides the hull direction net (identical
        semantics to ``ScoringEngine.score(hull_dirs=...)``) — the streaming
        maintainer passes the previous block's moment-derived net here.

        ``n_valid``: pass when ``Y`` was pre-staged with ``stage_rows`` —
        ``Y`` is then the already padded+sharded (n_pad, …) array and
        ``n_valid`` the true row count.

        ``sweep_ckpt``: directory (or ``CheckpointManager``-like object with
        ``.directory``) for resumable sweeps — the scan is split into
        segments of ``ft_config.sweep_ckpt_every_chunks`` chunks whose
        PER-SHARD partial state (Gram/moments or CountSketch, running hull
        extremes, scored rows, chunk cursor) checkpoints atomically between
        segments. ``resume=True`` picks up from the latest segment; because
        per-shard accumulation order is preserved and the cross-shard
        reduction runs once at the end, the resumed result is bit-identical
        to the uninterrupted segmented run (same mesh/chunk layout required).
        """
        if method not in SCORE_METHODS:
            raise ValueError(f"unknown scoring method: {method}")
        if hull_k > 0 and hull_key is None:
            raise ValueError("hull_k > 0 requires hull_key")
        if hull_dirs is not None and hull_k <= 0:
            raise ValueError("hull_dirs requires hull_k > 0")
        strat = resolve_strategy(
            strategy,
            sketch_size=sketch_size,
            gram_dtype=gram_dtype or self.gram_dtype,
        )
        if strat.needs_key and key is None:
            raise ValueError("sketch_size > 0 requires key")
        if isinstance(strat, TwoPassSketched):
            raise NotImplementedError(
                "TwoPassSketched is not sharded (a sketch caller has already "
                "accepted constant-factor scores — use the one-pass strategy)"
            )
        f64 = isinstance(strat, TwoPassExact) and strat.gram_dtype == "float64"
        if f64 and not jax.config.jax_enable_x64:
            raise ValueError(
                "gram_dtype='float64' on the sharded engine carries the Gram "
                "in f64 inside the mesh and requires x64 mode "
                "(JAX_ENABLE_X64=1); the single-host engine accumulates "
                "host-side instead and needs no flag"
            )
        if getattr(strat, "gram_dtype", "float32") == "float64" and not f64:
            # the sharded one-pass carries (and psums) an f32 CountSketch —
            # refuse a sketched f64 request instead of silently downcasting
            raise NotImplementedError(
                "gram_dtype='float64' sketched accumulation is single-host "
                "only (the sharded one-pass sweep carries an f32 sketch)"
            )
        r = self.rows_per_point
        hull = hull_k > 0

        if hull and int(np.shape(Y)[0]) * r > np.iinfo(np.int32).max:
            # the running-extreme carries hold global P-row ids as int32 (a
            # stable scan-carry dtype with or without x64); refuse loudly
            # instead of wrapping silently at pod-extreme n·r
            raise ValueError(
                "hull selection over more than 2^31-1 derivative rows would "
                "overflow the int32 hull-index carries; shard the input or "
                "reduce rows_per_point"
            )
        if sweep_ckpt is not None:
            if n_valid is not None:
                raise ValueError(
                    "sweep_ckpt is incompatible with pre-staged inputs "
                    "(n_valid): the segmented driver stages rows per segment"
                )
            return self._score_segmented(
                strat, key, Y, weights, method, ridge_reg, hull_k, hull_key,
                sweep_ckpt, resume, hull_dirs=hull_dirs,
            )
        if n_valid is not None:
            n = int(n_valid)
            chunk, cps, n_pad = self._shard_layout(n)
            if int(Y.shape[0]) != n_pad:
                raise ValueError(
                    f"staged input has {Y.shape[0]} rows but the layout for "
                    f"n={n} needs {n_pad} (use stage_rows)"
                )
        else:
            n = int(np.shape(Y)[0])
            chunk, cps, n_pad = self._shard_layout(n)
        if n == 0:
            raise ValueError("cannot score an empty dataset")
        pad = n_pad - n
        # bytes staged: the padded rows (unless pre-staged), the mask and √w
        staged = 2 * n_pad * 4
        if n_valid is None:
            row_dtype = np.dtype(getattr(Y, "dtype", np.float32))
            staged += n_pad * int(np.prod(np.shape(Y)[1:])) * row_dtype.itemsize
        with TraceAnnotation("repro.scoring.stage", bytes=staged):
            if n_valid is not None:
                Y_pad = Y
            else:
                Y = jnp.asarray(Y)
                # pad with copies of row 0 (valid data — no NaN risk through
                # the featurizer); masks keep pads out of every statistic
                if pad:
                    Y_pad = jnp.concatenate(
                        [Y, jnp.broadcast_to(Y[:1], (pad,) + Y.shape[1:])], axis=0
                    )
                else:
                    Y_pad = Y
                Y_pad = self._shard_put(Y_pad)
            mask = (jnp.arange(n_pad) < n).astype(jnp.float32)
            sw = (
                jnp.sqrt(jnp.asarray(weights, jnp.float32))
                if weights is not None
                else jnp.ones((n,), jnp.float32)
            )
            swm = jnp.concatenate([sw, jnp.zeros((pad,), jnp.float32)]) if pad else sw

            mask = self._shard_put(mask)
            swm = self._shard_put(swm)
        shards = _num_shards(self.mesh, self.axes)

        score_fn = (
            self._score_one_pass
            if isinstance(strat, OnePassSketched)
            else self._score_two_pass
        )
        u, G_host, cand = score_fn(
            strat, key, Y_pad, swm, mask, n, n_pad, chunk, cps,
            method, ridge_reg, hull_k, hull_key, hull_dirs=hull_dirs,
        )
        with TraceAnnotation("repro.scoring.finalize"):
            # every distinct candidate row, first-occurrence order — matching
            # the single-host engine (truncation to k points happens at the
            # coreset assembly via exact_hull_points)
            hull_rows = None if cand is None else stable_first_unique(cand)
            return finalize_scoring(n, cps * shards, method, G_host, u, hull_rows, r)

    def _score_two_pass(
        self, strat, key, Y_pad, swm, mask, n, n_pad, chunk, cps,
        method, ridge_reg, hull_k, hull_key, hull_dirs=None,
    ):
        """The sharded exact two-pass sweep: (u, Gram, hull candidates)."""
        r = self.rows_per_point
        hull = hull_k > 0
        # ---- pass 1 (sharded, chunked): one fused psum of (G, Σp, Σppᵀ)
        with TraceAnnotation("repro.scoring.pass1"):
            pass1, pass2 = self._pass_fns(
                chunk, cps, hull, Y_pad.shape[1:], Y_pad.dtype,
                strat.gram_dtype,
            )
            G, s1, s2 = pass1(Y_pad, swm, mask)
        with TraceAnnotation("repro.scoring.gather.gram", bytes=G.nbytes):
            G_host = host_gather(G)

        # ---- between passes: (Jd)² host algebra, identical to single-host
        with TraceAnnotation("repro.scoring.projection"):
            V, inv = projection_from_gram(G_host, method, ridge_reg)

        cand = None
        if hull:
            if hull_dirs is None:
                with TraceAnnotation(
                    "repro.scoring.gather.moments", bytes=s1.nbytes + s2.nbytes
                ):
                    s1_host, s2_host = host_gather(s1), host_gather(s2)
            with TraceAnnotation("repro.scoring.directions"):
                if hull_dirs is not None:
                    dirs = np.asarray(hull_dirs, np.float32)
                else:
                    dirs = directions_from_moments(
                        hull_key, s1_host, s2_host, n * r, hull_k,
                        self.hull_oversample,
                    )
            with TraceAnnotation("repro.scoring.pass2"):
                u_pad, gimax, gimin = pass2(
                    Y_pad, swm, mask, V, inv, jnp.asarray(dirs)
                )
            with TraceAnnotation(
                "repro.scoring.gather.hull", bytes=gimax.nbytes + gimin.nbytes
            ):
                cand = np.concatenate(
                    [host_gather(gimax), host_gather(gimin)]
                ).astype(np.int64)
        else:
            with TraceAnnotation("repro.scoring.pass2"):
                u_pad = pass2(Y_pad, swm, V, inv)

        with TraceAnnotation("repro.scoring.gather.scores", bytes=u_pad.nbytes):
            u = host_gather(u_pad)[:n]
        return u, G_host, cand

    def _score_one_pass(
        self, strat, key, Y_pad, swm, mask, n, n_pad, chunk, cps,
        method, ridge_reg, hull_k, hull_key, hull_dirs=None,
    ):
        """The sharded one-pass sweep: ONE data pass, ONE fused state psum."""
        hull = hull_k > 0
        with TraceAnnotation("repro.scoring.plan"):
            fn, D = self._onepass_fn(
                chunk, cps, hull, Y_pad.shape[1:], Y_pad.dtype,
                strat.proj_size, strat.sketch_size,
            )
            # the global CountSketch plan — identical draws to the single-host
            # engine, so the two layouts emit the same estimates; pad entries
            # carry zero sign (and zero √w) so they cannot touch the sketch
            rows, signs, omega = strat.begin(n, D, key)
            pad = n_pad - n
            if pad:
                rows = jnp.concatenate([rows, jnp.zeros((pad,), rows.dtype)])
                signs = jnp.concatenate([signs, jnp.zeros((pad,), signs.dtype)])
            rows = self._shard_put(rows)
            signs = self._shard_put(signs)
        extras = ()
        if omega is not None:
            extras = extras + (omega,)
        if hull:
            with TraceAnnotation("repro.scoring.directions"):
                dirs1 = jnp.asarray(
                    hull_dirs
                    if hull_dirs is not None
                    else upfront_directions(
                        hull_key, self._p_rows_width(chunk, Y_pad),
                        hull_k, self.hull_oversample,
                    )
                )
            extras = extras + (dirs1,)

        with TraceAnnotation("repro.scoring.sweep"):
            outs = fn(Y_pad, swm, mask, rows, signs, *extras)
        z, SX = outs[:2]
        with TraceAnnotation("repro.scoring.gather.sketch", bytes=SX.nbytes):
            SX_host = host_gather(SX)
        with TraceAnnotation("repro.scoring.projection"):
            SXp = SX_host if omega is None else SX_host @ np.asarray(omega)
            V, inv = projection_from_gram(SXp.T @ SXp, method, ridge_reg)
            G_host = SX_host.T @ SX_host  # reported Gram: the full sketched Gram
        with TraceAnnotation("repro.scoring.readoff"):
            u_dev = _z_leverage_jit(z, V, inv)
        with TraceAnnotation("repro.scoring.gather.scores", bytes=u_dev.nbytes):
            u = host_gather(u_dev)[:n]
        cand = None
        if hull:
            gimax, gimin = outs[2], outs[3]
            with TraceAnnotation(
                "repro.scoring.gather.hull", bytes=gimax.nbytes + gimin.nbytes
            ):
                cand = np.concatenate(
                    [host_gather(gimax), host_gather(gimin)]
                ).astype(np.int64)
        return u, G_host, cand

    def _p_rows_width(self, chunk, Y_pad) -> int:
        """Width p of the featurizer's P rows (for the upfront net)."""
        sds = jax.ShapeDtypeStruct((chunk,) + Y_pad.shape[1:], Y_pad.dtype)
        _, P_s = jax.eval_shape(self.featurize, sds)
        if P_s is None:
            raise ValueError("hull_k > 0 requires a featurize that returns P rows")
        return int(P_s.shape[1])


def distributed_build_coreset(
    cfg,
    scaler,
    Y,
    k: int,
    method: str = "l2-hull",
    *,
    mesh: Mesh,
    key: jax.Array,
    axis="data",
    alpha: float = 0.8,
    sketch_size: int = 0,
    chunk_size: int | None = DEFAULT_CHUNK,
    sweep_ckpt=None,
    resume: bool = False,
):
    """Paper Algorithm 1 with the pre-sampling phase fully distributed.

    Same contract (and same key-split structure) as ``coreset.build_coreset``
    — returns a ``CoresetResult`` — but scoring runs on ``mesh`` through the
    ``DistributedScoringEngine``. ``sketch_size > 0`` routes through the
    fused one-pass sketched sweep (each row featurized exactly once).
    ``sweep_ckpt``/``resume``: resumable segmented scoring sweeps — see
    ``DistributedScoringEngine.score``. The sampling step after scoring is a
    pure function of ``key``, so a resumed build draws the same coreset.
    """
    from repro.core.coreset import CoresetResult, coreset_from_scoring

    with TraceAnnotation("repro.build"):
        t0 = time.perf_counter()
        Y = np.asarray(Y)
        n = Y.shape[0]
        k = min(k, n)

        if method == "uniform":
            idx = np.asarray(jax.random.choice(key, n, shape=(k,), replace=False))
            w = np.full(k, n / k)
            return CoresetResult(idx, w, None, method, time.perf_counter() - t0)

        # same 3-way split as build_coreset (k_score feeds the sketch plan) so
        # the two paths draw identical samples when their scores agree
        k_score, k_hull_key, k_draw = jax.random.split(key, 3)
        k_hull = k - int(np.floor(alpha * k)) if method == "l2-hull" else 0
        with TraceAnnotation("repro.build.engine"):
            engine = DistributedScoringEngine(
                cfg, scaler, mesh=mesh, axis=axis, chunk_size=chunk_size
            )
        rows = Y
        if sweep_ckpt is None:
            with TraceAnnotation("repro.build.put_rows", bytes=Y.nbytes):
                rows = jnp.asarray(Y)
        res = engine.score(
            rows,
            method=method,
            hull_k=k_hull,
            hull_key=k_hull_key,
            sketch_size=sketch_size,
            key=k_score if sketch_size > 0 else None,
            sweep_ckpt=sweep_ckpt,
            resume=resume,
        )
        return coreset_from_scoring(res, n, k, method, alpha, k_draw, t0)
