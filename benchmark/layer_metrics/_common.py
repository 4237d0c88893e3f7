"""The readers of the cell's main program (the train step; the decode
step), shared by the per-layer metrics that are named apart only because a
metric names ONE end-to-end metric it moves (samples/s in the train cells,
the inter-token gap in the decode cells). A reader returns None where the
trace holds nothing to read (the cpu rehearsal's pseudo-device may); the
harness then leaves the metric out. A metric filed under a cell whose
runner does not produce what it reads fails with the missing key's name."""
from __future__ import annotations

from .. import harness, trace as trace_mod


def main_step_seconds(run):
    """Median device-busy seconds per dispatch of the cell's main program
    (the one with most device time) on the busiest chip."""
    trace = run['trace']
    if not trace.devices:
        return None
    lo, hi = trace.window
    dev = max(trace.devices,
              key=lambda d: trace_mod.busy_seconds(d, lo, hi))
    _, times = trace_mod.main_program(dev, lo, hi)
    return harness.median(times) if times else None


def step_device_ms(run):
    s = main_step_seconds(run)
    return None if s is None else s * 1e3


def step_roofline(run):
    """The least time the chip could take for the step, by the
    configuration's own step_floor_seconds (its module says which roofline
    bounds it: BOUND), over the device time the step took, in percent. The
    runner supplies what the floor depends on as result['floor_arg']: the
    batch per chip of a train step, the K/V rows cached during the traced
    interval of a decode step."""
    s = main_step_seconds(run)
    if s is None:
        return None
    ctx = run['ctx']
    floor = ctx.model.step_floor_seconds(ctx.cfg, ctx.peaks,
                                         run['result']['floor_arg'])
    return 100.0 * floor / s
