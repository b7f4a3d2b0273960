"""Programs traced and lowered anew per call in the window: JAX's
jaxpr-to-MLIR events over the calls (each one a jit cache miss, then a
persistent-cache fetch or a compile)."""
from chipbench.run import LOWERING_EVENT


def read(ctx):
    if not ctx.results:
        return None
    return ctx.counters.get(LOWERING_EVENT, 0) / len(ctx.results)
