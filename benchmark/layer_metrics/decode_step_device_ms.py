"""Op lowerings / kernels: device-busy milliseconds per dispatch of the
decode step program (median over the traced window)."""
from ._common import step_device_ms as reduce  # noqa: F401
