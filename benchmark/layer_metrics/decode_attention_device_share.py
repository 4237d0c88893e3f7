"""Op lowerings / kernels: the share of the decode step's device time spent
in operations that a KV-cache attention op produced — the Fluid op types
kv_cache_attention / kv_block_attention (and their quant, chunk and verify
forms), which hold the gather of the K/V blocks, the re-layout of the
gathered views, the masked softmax and the weighted sum. An operation
belongs to the Fluid op whose type is a scope of its op_name, which the
program writes by lowering each op under jax.named_scope; a fusion carries
the op_name XLA kept for it. The K/V block gather itself is `jnp.take`, a
jitted library function: its HLO function is shared by its twelve call
sites, so its operations (and the re-layouts XLA derives from them) read a
bare 'gather' with NO scope at all (read on the chip, PR 23: 68.6-69.0 %
of the step). An unscoped gather is therefore counted in: the step
has no other gather of any size (the embedding lookup's is one row a
slot). Read in the dispatches of the cell's main program on the busiest
chip. None where the trace holds no provenance (no device plane: the cpu)
or no operation that matches."""
import bisect
import re

from .. import trace as trace_mod
from . import _spans, _xplane_meta

ATTENTION = re.compile(r'/kv_\w*attention\w*/')
# a gather under no Fluid op's scope: bare ('gather:', as the chip's traces
# print the shared function's) or under transformation wrappers only
UNSCOPED_GATHER = re.compile(r'^(?:[\w.]+\([^/]*\)/)*gather:')


def scope_share(trace, path, pattern, also=None):
    """Percent of the main program's operation time, on the busiest chip
    inside the window, whose op_name matches `pattern` (or `also`, which
    counts only where `pattern` matched something: a program that names
    no op has nothing to read); None if none."""
    dev = _spans.busiest_device(trace)
    if dev is None or not dev.ops or not path:
        return None
    prov = _xplane_meta.op_provenance(path).get(dev.name)
    if not prov:
        return None
    lo, hi = trace.window
    name, _ = trace_mod.main_program(dev, lo, hi)
    spans = sorted((s, e) for s, e, n in dev.modules
                   if n == name and s >= lo and e <= hi)
    starts = [s for s, _ in spans]
    hit = {n: bool(pattern.search(p)) for n, p in prov.items()}
    if also is not None and any(hit.values()):
        hit.update((n, True) for n, p in prov.items() if also.search(p))
    total = inside = 0
    for s, e, n in dev.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or e > spans[i][1]:
            continue
        total += e - s
        if hit.get(n):
            inside += e - s
    return 100.0 * inside / total if inside else None


def reduce(run):
    return scope_share(run['trace'],
                       getattr(run['ctx'].tracer, 'path', None), ATTENTION,
                       also=UNSCOPED_GATHER)
