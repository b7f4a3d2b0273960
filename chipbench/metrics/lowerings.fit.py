"""Programs traced and lowered anew per fit in the window: the reading of
``lowerings.build``, a fit being the fit cell's call (each fit builds its own
jitted oracles)."""
from chipbench.readers import lowerings_per_call as read
