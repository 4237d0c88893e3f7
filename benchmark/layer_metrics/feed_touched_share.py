"""Decode scheduler: how much of the step's feed the host re-wrote — of the
rows live in the decode steps of the traced interval, the share the
scheduler wrote again for that step. The program keeps the step's feed
(tokens, positions, block tables) between ticks and re-writes a row only at
an event of that row: its first step, a position that opens a block or moves
a window, every step of a row the host alone can advance (a beam's, a
drafter's, one admitted on a prefix hit). The step's 'decode/build_feed'
span carries `active` (rows live in the step) and `touched` (rows re-written
for it); the span is opened for other parts of the feed too, without the
stats. 100 x the sum of `touched` over the sum of `active`. None where no
span has the stat: the parent of the PR that added it, a window with no
live row."""
from . import _spans


def reduce(run):
    feeds = [st for st in _spans.span_stats(run, 'decode/build_feed')
             if 'touched' in st and 'active' in st]
    active = sum(int(st['active']) for st in feeds)
    if not active:
        return None
    return 100.0 * sum(int(st['touched']) for st in feeds) / active
