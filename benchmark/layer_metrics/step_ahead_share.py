"""Decode scheduler: how often the scheduler ran a step ahead of its reads —
the share of the decode steps dispatched in the traced interval whose feed
was built while the programs of the tick before were still unread. The
program opens 'decode/step' twice for one step; the dispatch half carries
the stat `ahead` (1: dispatched with a read outstanding, 0: everything was
read first — the first step after an idle spell, or every step while a beam
row is live or a drafter is attached), the read half does not. 100 x the
spans with ahead = 1 over the spans that have the stat. None where no span
has it: the parent of the PR that added it, a window with no step."""
from . import _spans


def reduce(run):
    ahead = [int(st['ahead']) for st in _spans.span_stats(run, 'decode/step')
             if 'ahead' in st]
    if not ahead:
        return None
    return 100.0 * sum(1 for a in ahead if a == 1) / len(ahead)
