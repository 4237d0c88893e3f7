"""Reduction of a jax profiler trace (.xplane.pb) to the numbers the
benchmark reports. Kept with the benchmark so that every PR computes the
same number the same way; checked against a recorded trace in
benchmark/tests/.

What a TPU trace looks like (read by hand from a v5e trace, PR 22): one
plane per chip, '/device:TPU:<n>', whose line 'XLA Ops' holds one event per
HLO operation that ran on the core and whose line 'XLA Modules' holds one
event per dispatch of a compiled program, named '<jit name>(<fingerprint>)'
(every program the repo's AOT path loads is 'jit_call', so the fingerprint
is what tells the decode step from a prefill slice); one
plane '/host:CPU' with one line per host thread, holding jax's own TraceMe
events and the benchmark's TraceAnnotation spans ('bench/...'). All planes
are on one time axis (nanoseconds), but the device's clock ran about 1.2 ms
ahead of the host's in the recorded trace: a program shows on the device
1.1 ms before the host span that launched it begins. Busy time, program
time and gap lengths are device-side and unaffected; the LABEL of a gap
shorter than a few milliseconds can be off by one host span.

On the cpu backend there is no device plane: XLA:CPU's operations appear on
host thread-pool lines as events carrying an 'hlo_op' stat. The rehearsal
reads those as one pseudo-device so that the same code path runs end to
end here; a number from it is never a device number.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

_DEVICE_PLANE = re.compile(r'^/device:(TPU|GPU):(\d+)$')
_OPS_LINE = 'XLA Ops'
_MODULES_LINE = 'XLA Modules'
_COLLECTIVE = re.compile(
    r'all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute'
    r'|collective-broadcast', re.I)
_OP_TEXT = re.compile(r'^(%[\w.\-]+) = (.*?) ([\w\-]+)\(')
_LAYOUT = re.compile(r'\{[^{}]*\}')
WINDOW_SPAN = 'bench/traced_window'
SPAN_PREFIX = 'bench/'


class Device(object):
    """One chip's timeline: ops and module dispatches as (start_ns,
    end_ns, name), sorted by start."""

    def __init__(self, name, ops, modules):
        self.name = name
        self.ops = sorted(ops)
        self.modules = sorted(modules)


class Trace(object):
    def __init__(self, devices, host, window):
        self.devices = devices      # [Device]
        self.host = host            # [(start_ns, end_ns, name, thread)]
        self.window = window        # (lo_ns, hi_ns) of bench/traced_window


def find_xplane(trace_dir):
    """The newest .xplane.pb under a jax.profiler.start_trace directory."""
    paths = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    if not paths:
        raise FileNotFoundError('no .xplane.pb under %s' % trace_dir)
    return max(paths, key=os.path.getmtime)


def load(path):
    """Parse one .xplane.pb (jax.profiler.ProfileData, nothing else)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, cpu_ops = [], [], []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == _MODULES_LINE:
                    modules = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                               for e in line.events]
            devices.append((int(m.group(2)),
                            Device(plane.name, ops, modules)))
        elif plane.name.startswith('/host:CPU'):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name, line.name))
                    if not e.duration_ns or e.name.startswith('end: '):
                        continue
                    stats = dict(e.stats)
                    if 'hlo_op' in stats:
                        cpu_ops.append((e.start_ns,
                                        e.start_ns + e.duration_ns, e.name,
                                        str(stats.get('hlo_module', '?')),
                                        stats.get('run_id', 0)))
    devices = [d for _, d in sorted(devices, key=lambda p: p[0])]
    if not devices and cpu_ops:
        devices = [_cpu_pseudo_device(cpu_ops)]
    window = None
    for s, e, name, _ in host:
        if name == WINDOW_SPAN:
            window = (s, e)
    if window is None:
        spans = [(s, e) for d in devices for s, e, _ in d.ops]
        if not spans:
            raise ValueError('trace holds neither the %s span nor any '
                             'device operation' % WINDOW_SPAN)
        window = (min(s for s, _ in spans), max(e for _, e in spans))
    return Trace(devices, sorted(host), window)


def _cpu_pseudo_device(cpu_ops):
    runs = collections.OrderedDict()
    for s, e, _, module, run in cpu_ops:
        lo, hi = runs.get((module, run), (s, e))
        runs[(module, run)] = (min(lo, s), max(hi, e))
    return Device('cpu:pseudo',
                  [(s, e, n) for s, e, n, _, _ in cpu_ops],
                  [(lo, hi, mod) for (mod, _), (lo, hi) in runs.items()])


# -- interval arithmetic -----------------------------------------------------

def union(intervals):
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of the disjoint sorted intervals a not covered by b."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- reductions --------------------------------------------------------------

def busy_intervals(dev, lo, hi):
    spans = dev.ops if dev.ops else dev.modules
    return union(clip([(s, e) for s, e, _ in spans], lo, hi))


def busy_seconds(dev, lo, hi):
    return total(busy_intervals(dev, lo, hi)) / 1e9


def mean_busy_seconds(trace):
    """Device-busy seconds inside the traced window, averaged over the
    chips that ran anything (the contract's device.busy_s)."""
    lo, hi = trace.window
    per = [busy_seconds(d, lo, hi) for d in trace.devices]
    per = [b for b in per if b > 0]
    return sum(per) / len(per) if per else 0.0


def window_seconds(trace):
    return (trace.window[1] - trace.window[0]) / 1e9


def idle_gaps(dev, lo, hi):
    return subtract([(lo, hi)], busy_intervals(dev, lo, hi))


def program_times(dev, lo, hi):
    """{program name: [device-busy seconds of each dispatch]} for the
    dispatches that lie wholly inside [lo, hi]. Busy time of a dispatch is
    the union of the operations inside its span (its span where the trace
    has no operation line)."""
    busy = busy_intervals(dev, lo, hi)
    starts = [s for s, _ in busy]
    out = collections.OrderedDict()
    for s, e, name in dev.modules:
        if s < lo or e > hi:
            continue
        if dev.ops:
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            t = 0
            while i < len(busy) and busy[i][0] < e:
                t += max(0, min(busy[i][1], e) - max(busy[i][0], s))
                i += 1
        else:
            t = e - s
        out.setdefault(name, []).append(t / 1e9)
    return out


def main_program(dev, lo, hi):
    """(name, [seconds per dispatch]) of the program with most device time
    in the window: the train step in a train cell, the decode step in a
    decode cell (a prefill slice is one slot wide, the step all of them)."""
    progs = program_times(dev, lo, hi)
    if not progs:
        return None, []
    name = max(progs, key=lambda n: sum(progs[n]))
    return name, progs[name]


def collective_exposed_seconds(dev, lo, hi):
    """Seconds with a collective running and no compute operation running
    on this chip."""
    coll = union(clip([(s, e) for s, e, n in dev.ops
                       if _COLLECTIVE.search(n)], lo, hi))
    comp = union(clip([(s, e) for s, e, n in dev.ops
                       if not _COLLECTIVE.search(n)], lo, hi))
    return total(subtract(coll, comp)) / 1e9


def short_op(name):
    """'%fusion.2 = f32[16384,16,512] fusion' from the HLO text the TPU
    trace prints as an operation's name (layouts and operands dropped)."""
    m = _OP_TEXT.match(name)
    if not m:
        return name[:96]
    shape = _LAYOUT.sub('', m.group(2))
    if len(shape) > 48:
        shape = shape[:45] + '...'
    return '%s = %s %s' % (m.group(1), shape, m.group(3))


def top_ops(trace, n=10):
    """[[op name, seconds]] of the n operations with most device time in
    the window, averaged over the chips."""
    lo, hi = trace.window
    acc = collections.Counter()
    for d in trace.devices:
        for s, e, name in d.ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                acc[name] += e - s
    k = max(len(trace.devices), 1)
    return [[short_op(name), t / 1e9 / k]
            for name, t in acc.most_common(n)]


_LABELLED_GAPS = 200       # the longest gaps get a label; the rest are lumped
_MIN_HOST_EVENT_NS = 20000  # shorter host events cannot explain a long gap
_LOOKBACK = 4000


def _label_index(trace):
    """Host events that can label a gap, sorted by start."""
    cands = [(s, e, name, thread) for s, e, name, thread in trace.host
             if name != WINDOW_SPAN and (name.startswith(SPAN_PREFIX)
                                         or e - s >= _MIN_HOST_EVENT_NS)]
    return cands, [c[0] for c in cands]


def _host_label(index, t):
    """What the host was doing at trace time t: the innermost open
    benchmark span, else the innermost other host event with its thread."""
    cands, starts = index
    i = bisect.bisect_right(starts, t)
    best = other = None
    for s, e, name, thread in reversed(cands[max(0, i - _LOOKBACK):i]):
        if e <= t:
            continue
        if name.startswith(SPAN_PREFIX):
            best = name        # scanning backwards: the first is innermost
            break
        if other is None:
            other = 'unattributed:%s/%s' % (thread, name)
    if best is not None:
        return best
    return other if other is not None else 'unattributed'


def idle_by_host_activity(trace, n=10):
    """[[label, seconds]]: idle time of the busiest chip's timeline inside
    the window, summed by what the host was doing at the middle of each
    gap, largest first. Only the longest gaps are labelled one by one; the
    many sub-microsecond gaps between back-to-back operations are lumped
    under 'short_gaps'."""
    lo, hi = trace.window
    if not trace.devices:
        return []
    dev = max(trace.devices, key=lambda d: busy_seconds(d, lo, hi))
    gaps = sorted(idle_gaps(dev, lo, hi), key=lambda g: g[0] - g[1])
    index = _label_index(trace)
    acc = collections.Counter()
    for s, e in gaps[:_LABELLED_GAPS]:
        acc[_host_label(index, (s + e) // 2)] += e - s
    rest = total(gaps[_LABELLED_GAPS:])
    if rest:
        acc['short_gaps'] += rest
    return [[label, t / 1e9] for label, t in acc.most_common(n)]
