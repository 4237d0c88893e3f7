"""Decode scheduler: milliseconds per dispatch spent giving back the
window layers' blocks that every live window has passed and adding the
blocks the next dispatch writes — the program's span
'decode/window_release' (inside 'decode/build_feed' for the step, inside
'decode/prefill_slice' for a slice; stats `blocks` returned, `slots`
looked at) over tick_host_ms's own denominator, step + prefill-slice
dispatches in the traced interval. None where the program has no such
span: a model without window layers, the parent of the PR that added
them."""
from . import _spans


def reduce(run):
    return _spans.tick_phase_ms(run, ('decode/window_release',))
