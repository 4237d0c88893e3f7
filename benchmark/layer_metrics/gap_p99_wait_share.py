"""Decode scheduler: who owns the tail of the inter-token gap, the device
or the host — over the gaps between two steps' deliveries at or above
their p99 (emit_gap_ms_p99), the share of their seconds the scheduler's
thread stood in block_until_ready between the two deliveries, in percent,
tracing off: `wait_slice_s` of the tick that OPENED the gap (its reads of
prompts' last slices follow its delivery) plus `wait_step_s` of the tick
that closed it (its wait for the step's ids is in front of its delivery),
over the sum of the gaps. Near 100 where the device is the slower side —
the wait IS the pipeline, and the tail is what the device ran: a step and a
slice; low where the host's own work between two deliveries (the
consumers' GIL time, the dispatch calls) is the gap. None where the tick
log lacks the columns (the parent of the PR that added them) or the window
holds too few deliveries for a p99."""
import numpy as np

from .. import harness
from . import _requests


def reduce(run):
    tail = _requests.p99_gaps(run)
    if tail is None:
        return None
    rows, closing, gap, p99 = tail
    behind = rows['wait_slice_s'][closing - 1]
    before = rows['wait_step_s'][closing]
    # a stop from outside (the process not running) is a long gap with no
    # wait in it: a few of them pull the share of the sums down, so the
    # line also gives the share in the tail's median gap
    harness.say('  the gaps at or above p99, a gap', gaps=len(closing),
                gap_ms=float(gap.mean()) * 1e3,
                median_gap_ms=float(np.median(gap)) * 1e3,
                median_share=100.0 * float(np.median((behind + before) / gap)),
                wait_for_slices_ms=float(behind.mean()) * 1e3,
                wait_for_the_step_ms=float(before.mean()) * 1e3,
                tick_wall_ms=float(rows['wall_s'][closing].mean()) * 1e3)
    return 100.0 * float(behind.sum() + before.sum()) / float(gap.sum())
