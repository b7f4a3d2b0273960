"""Share of the traced window in which no operation ran on the device (%):
the reading of ``idle_share.build``, over the fit cell's window."""
from chipbench.readers import idle_share as read
