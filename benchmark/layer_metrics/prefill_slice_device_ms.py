"""Op lowerings / kernels: device-busy milliseconds of one dispatch of the
configuration's LARGEST prefill chunk program (jit_prefill_chunk_<C>; its
row form jit_prefill_chunk_<C>x<R> counts too where a spec holds one), the
median over the dispatches that lie inside the traced window on the busiest
chip. In a closed-loop cell a stream's longest gaps are a decode step plus
the slice that shared its tick, so this is the other half of itl_p99_ms.
None where the window holds no such dispatch (a few seconds of a cell
whose requests live half a minute hold about a dozen), the trace has no
device plane, or the configuration's module names no chunk sizes."""
import re

from .. import harness, trace as trace_mod
from . import _spans


def reduce(run):
    trace, ctx = run['trace'], run['ctx']
    sizes = getattr(ctx.model, 'chunk_sizes', None)
    if trace is None or sizes is None:
        return None
    dev = _spans.busiest_device(trace)
    if dev is None:
        return None
    largest = re.compile(r'prefill_chunk_0*%d(?:x\d+)?(?!\d)'
                         % max(sizes(ctx.cfg)))
    lo, hi = trace.window
    times = [t for name, ts in trace_mod.program_times(dev, lo, hi).items()
             if largest.search(name) for t in ts]
    return harness.median(times) * 1e3 if times else None
