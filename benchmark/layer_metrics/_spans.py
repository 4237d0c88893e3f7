"""How the per-layer readers get at the PROGRAM's own spans
(paddle_tpu.profiler.span: 'decode/...', 'exe/...', 'compile/...',
'load/...', 'pass/...'), which land in the benchmark's trace because a
TraceAnnotation lands in whatever jax profiler trace is running.

`trace.load` keeps every host event as (start_ns, end_ns, name, thread) in
`run['trace'].host` and drops its stats; `span_stats` re-opens the
.xplane.pb (`run['ctx'].tracer.path`) with jax.profiler.ProfileData for the
readers that need a span's stats ('bytes', 'waited_us', 'program').

A program that has no such span (the parent of the PR that added them) gives
every reader here nothing to read: they return None and the harness leaves
the metric out. benchmark/layer_metrics/README.md says how to add a reader.
"""
from __future__ import annotations

import bisect

from .. import harness, trace as trace_mod

PREFIXES = ('decode/', 'exe/', 'compile/', 'load/', 'pass/')
# spans that only hold other spans: time under them and under no child is
# bookkeeping between phases, not a phase
CONTAINERS = ('decode/tick', 'decode/step', 'decode/prefill_slice',
              'decode/admit', 'exe/run', 'exe/run_steps')
DISPATCH = ('decode/dispatch', 'exe/dispatch')
# spans of the callers' threads: every python thread's line in the trace
# is called after the process ('python3'), so a thread is told from the
# scheduler's by what it runs, not by its name
CALLER_SIDE = ('decode/submit',)
# the phases of a decode tick that have a metric of their own
TICK_FEED = ('decode/build_feed', 'decode/dispatch')
TICK_D2H = ('decode/d2h',)
TICK_ADVANCE = ('decode/advance', 'decode/first_token')
TICK_WAIT = ('decode/device_wait',)


def named(trace, names, whole=False):
    """[(start_ns, end_ns, name, thread)] of the host events called one of
    `names` that overlap the traced window (whole=True: lie inside it)."""
    lo, hi = trace.window
    return [(s, e, n, t) for s, e, n, t in trace.host
            if n in names and ((s >= lo and e <= hi) if whole
                               else (e > lo and s < hi))]


def _clipped(trace, spans):
    lo, hi = trace.window
    return trace_mod.union(trace_mod.clip([(s, e) for s, e, _, _ in spans],
                                          lo, hi))


def dispatching_thread(trace):
    """The thread that launches the device programs: the one with most
    dispatch spans in the window (the decode scheduler's thread; the
    thread that calls Executor.run). None when the program has no span."""
    count = {}
    for _, _, _, thread in named(trace, DISPATCH):
        count[thread] = count.get(thread, 0) + 1
    return max(count, key=count.get) if count else None


def busiest_device(trace):
    lo, hi = trace.window
    if not trace.devices:
        return None
    return max(trace.devices,
               key=lambda d: trace_mod.busy_seconds(d, lo, hi))


# -- the shared clock --------------------------------------------------------

_PAIR_TOLERANCE_NS = 3000000


def clock_offset_ns(trace, dispatch=DISPATCH):
    """Device clock minus host clock in this trace, as far as the trace can
    show it: the minimum, over the programs that start on the busiest chip
    inside the window, of (device start of the program - start of the
    dispatch span that launched it). The launching span is taken to be the
    one whose start is nearest the program's, among those that began no
    more than 3 ms after the program shows (the skew seen so far is 1.2 ms;
    a scheduler's dispatches lie further apart than that). A device cannot
    start before it is asked, so a negative minimum is skew; a positive one
    is launch latency, or a queue of programs in flight (training), and
    says nothing — so the estimate is never above 0, and with a queue it
    can be wrong by at most the 3 ms. None without dispatch spans."""
    dev = busiest_device(trace)
    starts = sorted(s for s, _, _, _ in named(trace, dispatch))
    if dev is None or not starts:
        return None
    lo, hi = trace.window
    best = None
    for m, _, _ in dev.modules:
        if m < lo or m > hi:
            continue
        j = bisect.bisect_left(starts, m)       # first start at or after m
        near = [starts[k] for k in (j - 1, j)
                if 0 <= k < len(starts)
                and starts[k] <= m + _PAIR_TOLERANCE_NS]
        if not near:
            continue
        d = m - min(near, key=lambda h: abs(m - h))
        best = d if best is None else min(best, d)
    return None if best is None else min(best, 0)


# -- a decode tick's phases --------------------------------------------------

def tick_dispatches(run):
    """tick_host_ms's own denominator: step + prefill-slice dispatches in
    the traced interval, from the DecodeStats deltas."""
    c = run['result']['counters_traced']
    return c['steps'] + c['chunk_slices']


def tick_phase_ms(run, names):
    """Milliseconds per dispatch inside the spans `names` of the scheduler
    thread, over the traced window. None when the trace has no such span
    or the interval held no dispatch."""
    trace = run['trace']
    n = tick_dispatches(run)
    spans = named(trace, names)
    if not spans or not n:
        return None
    return trace_mod.total(_clipped(trace, spans)) / 1e9 / n * 1e3


def tick_rest_ms(run):
    """Everything in a tick that is none of the phases nor the wait for the
    device: decode/tick's own time, expire, admit, slice bookkeeping."""
    trace = run['trace']
    ticks = named(trace, ('decode/tick',))
    n = tick_dispatches(run)
    if not ticks or not n:
        return None
    phases = named(trace,
                   TICK_FEED + TICK_D2H + TICK_ADVANCE + TICK_WAIT)
    rest = trace_mod.total(trace_mod.subtract(_clipped(trace, ticks),
                                              _clipped(trace, phases)))
    return rest / 1e9 / n * 1e3


# -- idle time, attributed ---------------------------------------------------

def innermost_segments(spans):
    """Properly nested spans of ONE thread, flattened to disjoint
    (start, end, name of the innermost span open there), sorted."""
    out, stack = [], []         # stack of (end, name), outermost first
    cur = 0                     # segments are written up to here

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
            cur = max(cur, end)

    for s, e, name, _ in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack and s > cur:
            out.append((cur, s, stack[-1][1]))
        stack.append((e, name))
        cur = s
    close_until(float('inf'))
    return out


def idle_attribution(trace):
    """(idle seconds of the busiest chip whose gap's midpoint, moved onto
    the host's clock, lies in a program span of the dispatching thread that
    is a phase and not a container; idle seconds; {span name: seconds};
    the clock offset used, ns). None when the trace has no device or the
    program no span."""
    dev = busiest_device(trace)
    thread = dispatching_thread(trace)
    if dev is None or thread is None:
        return None
    offset = clock_offset_ns(trace) or 0
    lo, hi = trace.window
    segs = innermost_segments(
        [x for x in trace.host
         if x[3] == thread and x[2].startswith(PREFIXES)
         and x[2] not in CALLER_SIDE])
    starts = [s for s, _, _ in segs]
    by_name, idle = {}, 0
    for a, b in trace_mod.idle_gaps(dev, lo, hi):
        idle += b - a
        t = (a + b) // 2 - offset
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and segs[i][1] > t:
            name = segs[i][2]
            by_name[name] = by_name.get(name, 0) + (b - a)
    named_s = sum(v for k, v in by_name.items() if k not in CONTAINERS)
    return (named_s / 1e9, idle / 1e9,
            {k: v / 1e9 for k, v in by_name.items()}, offset)


# -- stats of a span ---------------------------------------------------------

def _read_span_stats(run):
    """{span name: [(start_ns, stats dict)]} of the program spans that
    overlap the traced window, from the trace file."""
    out = {}
    path = getattr(run['ctx'].tracer, 'path', None)
    if not path:
        return out
    from jax.profiler import ProfileData
    lo, hi = run['trace'].window
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith('/host:CPU'):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES) and e.start_ns < hi \
                        and e.start_ns + e.duration_ns > lo:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))
    return out


def span_stats(run, name):
    """[stats dict] of the spans called `name` that overlap the traced
    window, in time order: the .xplane.pb read again (once a run), because
    trace.load keeps no stats. [] when there is no such span (or no trace
    file: a hand-made Trace)."""
    if '_span_stats' not in run:
        run['_span_stats'] = _read_span_stats(run)
    return [st for _, st in sorted(run['_span_stats'].get(name, ()),
                                   key=lambda x: x[0])]


def stat_median(run, name, key, where=None):
    vals = [st[key] for st in span_stats(run, name)
            if key in st and (where is None or where(st))]
    return harness.median(vals) if vals else None


# -- the executor's call -----------------------------------------------------

def exe_runs(trace):
    """[(exe/run seconds, its exe/dispatch child's seconds)] of the run
    calls that lie inside the window."""
    out = []
    dispatches = named(trace, ('exe/dispatch',), whole=True)
    for s, e, _, thread in named(trace, ('exe/run', 'exe/run_steps'),
                                 whole=True):
        inside = [de - ds for ds, de, _, dt in dispatches
                  if dt == thread and ds >= s and de <= e]
        if inside:
            out.append(((e - s) / 1e9, sum(inside) / 1e9))
    return out


# -- what tracing costs while it is on ---------------------------------------

def say_tracing_cost(run):
    """Prints (never a metric) what the traced interval cost the decode
    cell that is running: the consumer-side inter-token gap and tokens/s
    inside [rate end, window close] against the part of the window before
    it, which is what the run's rates come from."""
    r, runner = run['result'], run['runner']
    records = [x for x in getattr(runner, 'records', ()) if x]
    if not records or not r.get('itl_ms') or 'counters_traced' not in r:
        return
    t_rate_end = r['t_open'] + r['window_s']
    inside = [(b - a) * 1e3 for x in records
              for a, b in zip(x['times'], x['times'][1:])
              if t_rate_end <= b < r['t_close']]
    if not inside:
        return
    harness.say(
        'cost of tracing while on (inside the traced interval / before)',
        itl_p50_ms_inside=harness.median(inside),
        itl_p50_ms_before=harness.median(r['itl_ms']),
        tokens_per_s_inside=r['counters_traced']['tokens']
        / trace_mod.window_seconds(run['trace']),
        tokens_per_s_before=r['tokens_per_s'])
