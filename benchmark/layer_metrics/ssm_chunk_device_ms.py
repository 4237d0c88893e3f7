"""Op lowerings / kernels: device milliseconds under a state-space layer's
state movers (state_space/selective_scan/ — in a prefill slice the
recurrence's CHUNK form from a carried state to a carried state — and
state_space/conv/, the convolution over the slice from its carried tail) in
one dispatch of the configuration's LARGEST prefill chunk program
(jit_prefill_chunk_<C>), summed over the layers; the median over the
dispatches that lie inside the traced window on the busiest chip — the
state-space layers' part of prefill_slice_device_ms, which since a tick
dispatches at most one such slice is in every p99 gap. None where the window
holds no such dispatch, the trace has no provenance, the program has no such
scope, or the configuration's module names no chunk sizes."""
import re

from .linear_attention_roofline import scope_seconds
from .ssm_scan_roofline import STATE_MOVERS


def largest_chunk_program(ctx):
    """A pattern for the names of the configuration's largest prefill chunk
    program (and its row form), or None where its module names no sizes."""
    sizes = getattr(ctx.model, 'chunk_sizes', None)
    if sizes is None:
        return None
    return re.compile(r'prefill_chunk_0*%d(?:x\d+)?(?!\d)'
                      % max(sizes(ctx.cfg)))


def reduce(run):
    trace, ctx = run['trace'], run['ctx']
    largest = largest_chunk_program(ctx)
    if trace is None or largest is None:
        return None
    seconds = scope_seconds(trace, getattr(ctx.tracer, 'path', None),
                            STATE_MOVERS, program=largest)
    return None if seconds is None else seconds * 1e3
