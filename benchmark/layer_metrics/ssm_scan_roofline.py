"""Op lowerings / kernels: the least time the chip could take to read AND
write the state one decode step's state-space (Mamba) layers carry, over
the device time the step spent moving it, in percent (a step of the scan is
bound by memory: a few operations a state byte). Needed bytes: the
configuration's own ssm_state_bytes(cfg, live) — each live slot's scan
state and convolution tail in every Mamba layer once read and once written
— at the traced interval's mean live rows (active slot-steps / steps): the
same bytes whatever implements the recurrence, so a later kernel is read by
this yardstick. Time: per dispatch of the cell's main program on the
busiest chip, the sum of the operations whose op_name lies under
state_space/selective_scan/ or state_space/conv/ — the two ops that move
the bytes counted (the scan its state, the convolution its tail: the share
is of what both took, so that it cannot pass 100 by leaving the tail's time
out); the median over the dispatches. None where the trace holds no
provenance, the program has no such scope, or the configuration's module
has no ssm_state_bytes."""
import re

from .linear_attention_roofline import scope_seconds

STATE_MOVERS = re.compile(r'/state_space/(?:selective_scan|conv)/')


def reduce(run):
    ctx = run['ctx']
    bytes_fn = getattr(ctx.model, 'ssm_state_bytes', None)
    if bytes_fn is None or run['trace'] is None:
        return None
    seconds = scope_seconds(run['trace'],
                            getattr(ctx.tracer, 'path', None), STATE_MOVERS)
    c = run['result'].get('counters_traced')
    if seconds is None or not c or not c['steps']:
        return None
    live = c['active_slot_steps'] / c['steps']
    floor = bytes_fn(ctx.cfg, live) / ctx.peaks['hbm_bytes_per_s']
    return 100.0 * floor / seconds
