"""Op lowerings / kernels: the least time the chip could take for its share
of a train step (required FLOPs / peak bf16 FLOP/s: the step is bound by
compute) over the device time the step took."""
from ._common import step_roofline as reduce  # noqa: F401
