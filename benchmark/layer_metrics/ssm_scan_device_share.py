"""Op lowerings / kernels: the share of the decode step's device time spent
under a state-space (Mamba) layer's selective scan — the discretisation,
the recurrence over the per-slot state and the read-out, which the program
lowers under state_space/selective_scan (models/phi4_flash.py:
fluid.name_scope, which core/lowering.py turns into jax.named_scope). Read
in the dispatches of the cell's main program on the busiest chip. None
where the trace holds no provenance (no device plane: the cpu) or the
program has no such scope."""
import re

from .decode_attention_device_share import scope_share

SELECTIVE_SCAN = re.compile(r'/state_space/selective_scan/')


def reduce(run):
    if run['trace'] is None:
        return None
    return scope_share(run['trace'],
                       getattr(run['ctx'].tracer, 'path', None),
                       SELECTIVE_SCAN)
