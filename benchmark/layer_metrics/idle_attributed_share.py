"""Device: the share of the busiest chip's idle time that has a name —
idle seconds whose gap's midpoint, moved onto the host's clock by the
trace's own skew estimate, lies in a program span of the dispatching thread
that is a phase (build_feed, dispatch, device_wait, d2h, advance, ...) and
not a container (tick, step, prefill_slice, admit), over idle seconds.
Prints the skew estimate, the seconds under each span, and what the traced
interval cost the run."""
from .. import harness
from . import _spans


def reduce(run):
    trace = run['trace']
    got = _spans.idle_attribution(trace)
    if got is None or not got[1]:
        return None
    named_s, idle_s, by_name, offset = got
    harness.say('host-device clock offset in this trace (device - host)',
                ns=offset)
    for name, s in sorted(by_name.items(), key=lambda kv: -kv[1]):
        harness.say('  idle under %s' % name, seconds=s,
                    share=s / idle_s)
    _spans.say_tracing_cost(run)
    return 100.0 * named_s / idle_s
