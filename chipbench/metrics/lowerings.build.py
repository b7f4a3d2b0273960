"""Programs traced and lowered anew per call in the window: JAX's
jaxpr-to-MLIR events over the calls (each one a jit cache miss, then a
persistent-cache fetch or a compile)."""
from chipbench.readers import lowerings_per_call as read
