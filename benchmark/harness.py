"""What every runner shares: the run's context, host spans, percentiles,
the cache root and the key of what is cached there, device facts.

Nothing here knows a configuration, a traffic mix or a metric by name:
those live in files of their own (benchmark/README.md).
"""
from __future__ import annotations

import contextlib
import hashlib
import importlib
import json
import math
import os
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# what the program's behaviour depends on: a cached artifact is keyed on
# these, so a PR that changes the program is never served its parent's
SOURCE_DIRS = ('paddle_tpu', 'models')


def say(msg, **fields):
    """A human-readable progress line on stdout (the result line is the
    LAST line and the only one the driver parses)."""
    extra = ' '.join('%s=%s' % (k, _fmt(v)) for k, v in fields.items())
    print('[bench %7.2fs] %s %s' % (time.perf_counter() - T0, msg, extra),
          flush=True)


def _fmt(v):
    return '%.4g' % v if isinstance(v, float) else str(v)


T0 = time.perf_counter()     # re-set by run.py at process start


def load_json(path):
    with open(path) as f:
        return json.load(f)


def overlay(base, over):
    """base with the keys of `over` replaced (nested dicts merged)."""
    out = dict(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = overlay(out[k], v)
        else:
            out[k] = v
    return out


def percentile(values, q, min_beyond=10):
    """The q-th percentile (linear interpolation). Refuses when fewer than
    `min_beyond` samples lie beyond it: a p95 over a dozen requests is a
    maximum (choosing-metrics, section 1)."""
    n = len(values)
    if n * (100.0 - q) / 100.0 < min_beyond:
        raise ValueError('p%g needs at least %d samples beyond it; have %d '
                         'samples in all' % (q, min_beyond, n))
    xs = sorted(values)
    pos = (n - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, n - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50, min_beyond=0)


class Spans(object):
    """Benchmark-side spans around the calls into the program: kept in
    memory as (start, end) on time.perf_counter, and — only while a trace
    is being taken — mirrored into the profiler's trace as
    'bench/<name>' so that device idle gaps can be attributed."""

    def __init__(self):
        self.by_name = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name):
        ann = None
        if self.annotate:
            from jax.profiler import TraceAnnotation
            ann = TraceAnnotation('bench/' + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.by_name.setdefault(name, []).append(
                (t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def durations(self, name, lo=None, hi=None):
        return [e - s for s, e in self.by_name.get(name, ())
                if (lo is None or s >= lo) and (hi is None or e <= hi)]


class Tracer(object):
    """Takes the device trace of ONE interval inside a --trace 1 run. The
    runner calls start() and stop() at the instants it chooses; between
    them the main thread sits inside the 'bench/traced_window' span, whose
    extent is the traced window on the trace's own clock."""

    def __init__(self, enabled, out_dir, spans):
        self.enabled = enabled
        self.out_dir = out_dir
        self.spans = spans
        self.path = None
        self._ann = None

    def start(self):
        if not self.enabled:
            return
        import shutil
        from jax import profiler
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = profiler.ProfileOptions()
        opts.python_tracer_level = 0     # jax's TraceMe events only
        opts.host_tracer_level = 2
        profiler.start_trace(self.out_dir, profiler_options=opts)
        self.spans.annotate = True
        self._ann = profiler.TraceAnnotation('bench/traced_window')
        self._ann.__enter__()

    def stop(self):
        if not self.enabled or self._ann is None:
            return
        from jax import profiler
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.spans.annotate = False
        profiler.stop_trace()
        from . import trace as _trace
        self.path = _trace.find_xplane(self.out_dir)


class Context(object):
    """One run of one cell."""

    def __init__(self, bench, cell, cfg, cfg_path, traffic, seed, seconds,
                 trace, rehearsal, model=None):
        self.bench, self.cell = bench, cell
        self.cfg, self.cfg_path, self.traffic = cfg, cfg_path, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.rehearsal = bool(trace), bool(rehearsal)
        self.chips = int(cell['chips'])
        self.spans = Spans()
        # everything a runner knows of the model comes through this module
        self.model = model or importlib.import_module(
            'benchmark.configs.' + cfg.get('model', cfg['name']))
        self.tracer = None          # set by run.py once jax is up
        self.devices = None
        self.peaks = None
        self.cache_root = None

    def trace_seconds(self):
        return min(float(self.traffic.get('trace_seconds', 3.0)),
                   self.seconds / 2.0)


def cache_root():
    """Where compiled programs and exported artifacts are kept: the
    directory JAX_COMPILATION_CACHE_DIR names, else the fixed
    <checkout>/.compile_cache (paddle_tpu.core.compile_cache's own rule —
    the path is part of jax's cache key, so it never moves)."""
    from paddle_tpu.core import compile_cache
    return compile_cache.cache_dir()


def source_key(cfg_path):
    """Hash of the configuration file and of every *.py the program is made
    of. Keys what the benchmark caches beside the compile cache."""
    h = hashlib.sha256()
    with open(cfg_path, 'rb') as f:
        h.update(f.read())
    for top in SOURCE_DIRS:
        for base, dirs, names in os.walk(os.path.join(ROOT, top)):
            dirs.sort()
            for n in sorted(names):
                if n.endswith('.py'):
                    p = os.path.join(base, n)
                    h.update(os.path.relpath(p, ROOT).encode())
                    with open(p, 'rb') as f:
                        h.update(f.read())
    return h.hexdigest()[:20]


def artifact_dir(ctx):
    return os.path.join(ctx.cache_root, 'benchmark_artifacts',
                        '%s-%s' % (ctx.cfg['name'],
                                   source_key(ctx.cfg_path)))


def memory_peak_bytes(devices):
    """Peak bytes on the fullest chip (0 on a backend that reports none).
    The TPU backend counts live buffers (bytes_in_use) apart from the
    scratch it reserves for a running program (bytes_reserved): ResNet-50's
    step holds 0.5 GB of buffers and reserves 9.2 GB (PERF.md, Findings
    PR 22). The peak is therefore the larger of the buffers' own peak and
    the buffers now live plus the largest reservation."""
    peak = 0
    for d in devices:
        st = d.memory_stats()
        if st:
            peak = max(peak, int(st.get('peak_bytes_in_use', 0)),
                       int(st.get('bytes_in_use', 0))
                       + int(st.get('peak_bytes_reserved', 0)))
    return peak


def tail(run, values, q):
    """A tail percentile of a run's samples: the rehearsal, whose windows
    are seconds long, is let off the ten-samples-beyond rule."""
    return percentile(values, q,
                      min_beyond=0 if run['ctx'].rehearsal else 10)
