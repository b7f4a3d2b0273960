"""RunSupervisor: bounded-retry orchestration around the pipeline loops.

The supervisor wraps an *attempt closure* — a function that (re)builds its
compute (sharded step fns, engine chunk loops) from a ``RunContext`` and runs
it to completion. Contract:

  * **What is retried.** Any ``RuntimeError`` raised by the attempt — that
    family covers ``InjectedFailure``, ``NonFiniteError`` and jax's
    ``JaxRuntimeError`` (dead peer / barrier timeout / device loss).
    ``ValueError``/``TypeError``/``KeyboardInterrupt`` and friends are
    programming or user errors and propagate immediately, as do
    ``NotImplementedError``/``RecursionError`` (RuntimeError subclasses that
    are never transient) and the ``JaxRuntimeError``s that would fail the
    same way on every attempt: a program the XLA or Mosaic compiler refuses,
    and one that does not fit in device memory (``RESOURCE_EXHAUSTED``).
  * **What triggers re-planning.** When a planner is attached, every retry
    consults ``ElasticPlanner.plan(n_alive)`` with the currently visible
    device count and rebuilds the mesh (``mesh_from_plan``, or a caller
    ``remesh`` hook) — so a shrunk device pool yields a degraded mesh with
    batch/LR rescaled per the plan. A ``NonFiniteError`` retry instead
    applies multiplicative LR backoff and does not re-plan (the hardware is
    fine; the optimization diverged).
  * **Recovery guarantees.** The attempt closure is responsible for resuming
    from the last atomic checkpoint when ``ctx.resume`` is set (the
    ``restore_train_state(shardings=)`` path re-shards params/opt-state onto
    the surviving mesh; scoring sweeps resume their chunk cursor
    bit-identically). After ``max_retries`` failed retries the supervisor
    aborts with a single diagnostic ``RuntimeError`` carrying the attempt
    history and, when a ``FailureSimulator`` is installed, its persistent
    injection log.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import numpy as np
from jax.errors import JaxRuntimeError

from repro.ft.config import FTConfig, get_ft_config
from repro.ft.failure import ElasticPlanner, MeshPlan, NonFiniteError

__all__ = ["RunContext", "RunSupervisor", "mesh_from_plan"]

# RuntimeError subclasses that are never transient infrastructure faults
_NON_RETRYABLE = (NotImplementedError, RecursionError)

# XLA status codes that repeat identically on every attempt: out of device
# memory, and programs the compiler rejects. Device loss and collective
# timeouts arrive under other codes (UNAVAILABLE, DEADLINE_EXCEEDED, ...).
_PERMANENT_XLA_CODES = (
    "RESOURCE_EXHAUSTED",
    "INVALID_ARGUMENT",
    "UNIMPLEMENTED",
    "FAILED_PRECONDITION",
)


def _is_permanent_xla_error(exc: BaseException) -> bool:
    """A compile refusal (XLA or Mosaic, whatever its status code) or an
    out-of-memory error: retrying would only repeat it after the backoff."""
    if not isinstance(exc, JaxRuntimeError):
        return False
    msg = str(exc)
    return msg.startswith(_PERMANENT_XLA_CODES) or "compile" in msg.lower()


def mesh_from_plan(plan: MeshPlan, devices=None):
    """Materialize a ``MeshPlan`` on the first ``plan.n_devices`` devices."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else list(jax.devices())
    if plan.n_devices > len(devs):
        raise RuntimeError(
            f"plan needs {plan.n_devices} devices, only {len(devs)} visible"
        )
    return Mesh(np.asarray(devs[: plan.n_devices]).reshape(plan.shape), plan.axes)


@dataclasses.dataclass
class RunContext:
    """What an attempt closure needs to (re)build its compute."""

    attempt: int = 0
    resume: bool = False         # True on every retry: restore from last checkpoint
    mesh: object = None          # current (possibly degraded) mesh, or None
    plan: Optional[MeshPlan] = None
    lr_scale: float = 1.0        # combined non-finite backoff × plan rescale
    batch_scale: float = 1.0     # plan.global_batch / base batch


class RunSupervisor:
    """Bounded retry + exponential backoff + elastic re-planning."""

    def __init__(
        self,
        *,
        label: str = "run",
        planner: Optional[ElasticPlanner] = None,
        mesh=None,
        devices_fn: Optional[Callable[[], int]] = None,
        remesh: Optional[Callable[[MeshPlan], object]] = None,
        config: Optional[FTConfig] = None,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self.label = label
        self.planner = planner
        self.mesh = mesh
        self.devices_fn = devices_fn
        self.remesh = remesh
        self.config = config
        self.sleep = sleep
        self.events: list[dict] = []

    # ------------------------------------------------------------------ retry

    @staticmethod
    def _retryable(exc: BaseException) -> bool:
        return (
            isinstance(exc, RuntimeError)
            and not isinstance(exc, _NON_RETRYABLE)
            and not _is_permanent_xla_error(exc)
        )

    def _n_alive(self) -> int:
        if self.devices_fn is not None:
            return int(self.devices_fn())
        import jax

        return len(jax.devices())

    def _diagnostic(self, cfg: FTConfig, last: BaseException) -> str:
        lines = [
            f"[{self.label}] retry budget exhausted after "
            f"{cfg.max_retries + 1} attempts: {type(last).__name__}: {last}",
            f"attempt history: {self.events}",
        ]
        if cfg.simulator is not None and cfg.simulator.log:
            lines.append(f"injection log: {cfg.simulator.log}")
        return "\n".join(lines)

    def run(self, attempt_fn: Callable[[RunContext], object]):
        """Run ``attempt_fn(ctx)`` to completion, retrying on RuntimeError."""
        cfg = self.config if self.config is not None else get_ft_config()
        ctx = RunContext(mesh=self.mesh)
        nf_scale = 1.0
        for attempt in range(cfg.max_retries + 1):
            ctx.attempt = attempt
            try:
                return attempt_fn(ctx)
            except Exception as exc:  # noqa: BLE001 — filtered below
                if not self._retryable(exc):
                    raise
                self.events.append(
                    {
                        "attempt": attempt,
                        "error": f"{type(exc).__name__}: {exc}",
                        "kind": "nonfinite" if isinstance(exc, NonFiniteError) else "failure",
                    }
                )
                if attempt >= cfg.max_retries:
                    raise RuntimeError(self._diagnostic(cfg, exc)) from exc
                delay = cfg.backoff_s(attempt)
                if delay > 0:
                    self.sleep(delay)
                plan_scale = 1.0
                if isinstance(exc, NonFiniteError):
                    nf_scale *= cfg.lr_backoff_factor
                    if ctx.plan is not None and cfg.rescale_lr:
                        plan_scale = ctx.plan.lr_scale
                elif self.planner is not None:
                    plan = self.planner.plan(self._n_alive())
                    ctx.plan = plan
                    ctx.mesh = self.remesh(plan) if self.remesh else mesh_from_plan(plan)
                    ctx.batch_scale = plan.global_batch / max(
                        self.planner.base_global_batch, 1
                    )
                    if cfg.rescale_lr:
                        plan_scale = plan.lr_scale
                    self.events[-1]["plan"] = {
                        "shape": plan.shape,
                        "axes": plan.axes,
                        "global_batch": plan.global_batch,
                        "lr_scale": plan.lr_scale,
                    }
                elif ctx.plan is not None and cfg.rescale_lr:
                    plan_scale = ctx.plan.lr_scale
                ctx.lr_scale = nf_scale * plan_scale
                ctx.resume = True
        raise AssertionError("unreachable")
