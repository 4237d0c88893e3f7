"""Op lowerings / kernels: the least time the chip could take for a decode
step (bytes the algorithm needs / peak HBM bytes/s: the step is bound by
memory) over the device time the step took. Needed bytes: the weights once
plus every K/V row the decoding requests hold (prompt + tokens delivered so
far, averaged over the traced window, from the benchmark's own record of
the streams), at the cache's dtype."""
from ._common import step_roofline as reduce  # noqa: F401
