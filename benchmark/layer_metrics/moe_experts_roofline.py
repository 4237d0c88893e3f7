"""Op lowerings / kernels: the least time the chip could take to read the
expert weights one decode step needs, over the device time the step's
grouped matmuls took, in percent (the step is bound by memory). Needed
bytes: the configuration's own moe_expert_bytes — the EXPECTED distinct
experts under uniform routing, E * (1 - (1 - k/E)^live), each with its
gate, up and down matrix, in every layer — at `live` = the mean live rows
of the traced interval (active slot-steps / steps, DecodeStats deltas).
Time: per dispatch of the cell's main program on the busiest chip, the sum
of the operations whose op_name lies under moe_topk_ffn/experts (the
scope the lowering gives its grouped matmuls and the SwiGLU between
them) or is an unscoped ragged dot (moe_ffn_device_share says why the
grouped matmuls' custom calls read so); the median over the dispatches.
None where the trace holds no provenance, the program names no
moe_topk_ffn op, or the configuration's module has no such function."""
import bisect
import re

from .. import harness, trace as trace_mod
from . import _spans, _xplane_meta
from .moe_ffn_device_share import MOE, UNSCOPED_RAGGED_DOT

EXPERTS = re.compile(r'/moe_topk_ffn/experts/')


def experts_seconds(trace, path):
    """Median, over the main program's dispatches inside the window on
    the busiest chip, of the seconds of operations under the experts
    scope; None if no operation is."""
    dev = _spans.busiest_device(trace)
    if dev is None or not dev.ops or not path:
        return None
    prov = _xplane_meta.op_provenance(path).get(dev.name)
    if not prov:
        return None
    if not any(MOE.search(p) for p in prov.values()):
        return None
    hit = {n for n, p in prov.items()
           if EXPERTS.search(p) or UNSCOPED_RAGGED_DOT.search(p)}
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
    bytes_fn = getattr(ctx.model, 'moe_expert_bytes', None)
    seconds = experts_seconds(run['trace'],
                              getattr(ctx.tracer, 'path', None))
    if bytes_fn is None or seconds is None:
        return None
    c = run['result']['counters_traced']
    if not c['steps']:
        return None
    live = c['active_slot_steps'] / c['steps']
    floor = bytes_fn(ctx.cfg, live) / ctx.peaks['hbm_bytes_per_s']
    return 100.0 * floor / seconds
