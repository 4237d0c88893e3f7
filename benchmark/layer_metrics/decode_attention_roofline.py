"""Op lowerings / kernels: the least time the chip could take to read the
K and V rows one decode step's attention needs, over the device time the
step's kv_block_attention ops took, in percent (paged decode attention is
bound by memory). Needed bytes: the configuration's own
attention_bytes(cfg, cached_rows, live) — every cached position once in
each full-attention layer, the last `window` positions of each live row in
each sliding-window layer — at the traced interval's cached rows (the
runner's floor_arg) and mean live rows (active slot-steps / steps). Time:
per dispatch of the cell's main program on the busiest chip, the sum of
the operations whose op_name lies under a kv_block_attention scope (the
scope the lowering gives the op: the paged Pallas kernel's custom call and
what XLA fused around it); the median over the dispatches. None where the
trace holds no provenance, the program names no such op, or the
configuration's module has no attention_bytes."""
import bisect
import re

from .. import harness, trace as trace_mod
from . import _spans, _xplane_meta

STEP_ATTENTION = re.compile(r'/kv_block_attention/')


def attention_seconds(trace, path):
    """Median, over the main program's dispatches inside the window on
    the busiest chip, of the seconds of operations under a
    kv_block_attention scope; None if no operation is."""
    dev = _spans.busiest_device(trace)
    if dev is None or not dev.ops or not path:
        return None
    prov = _xplane_meta.op_provenance(path).get(dev.name)
    if not prov:
        return None
    hit = {n for n, p in prov.items() if STEP_ATTENTION.search(p)}
    if not hit:
        return None
    lo, hi = trace.window
    name, _ = trace_mod.main_program(dev, lo, hi)
    spans = sorted((s, e) for s, e, n in dev.modules
                   if n == name and s >= lo and e <= hi)
    starts = [s for s, _ in spans]
    per_dispatch = [0] * len(spans)
    for s, e, n in dev.ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= spans[i][1] and n in hit:
            per_dispatch[i] += e - s
    per_dispatch = [t for t in per_dispatch if t]
    return harness.median(per_dispatch) / 1e9 if per_dispatch else None


def reduce(run):
    ctx = run['ctx']
    bytes_fn = getattr(ctx.model, 'attention_bytes', None)
    if bytes_fn is None or run['trace'] is None:
        return None
    seconds = attention_seconds(run['trace'],
                                getattr(ctx.tracer, 'path', None))
    c = run['result'].get('counters_traced')
    if seconds is None or not c or not c['steps']:
        return None
    live = c['active_slot_steps'] / c['steps']
    floor = (bytes_fn(ctx.cfg, run['result']['floor_arg'], live)
             / ctx.peaks['hbm_bytes_per_s'])
    return 100.0 * floor / seconds
