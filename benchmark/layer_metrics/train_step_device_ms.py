"""Op lowerings / kernels: device-busy milliseconds per dispatch of the
train step program (median over the traced window, busiest chip)."""
from ._common import step_device_ms as reduce  # noqa: F401
