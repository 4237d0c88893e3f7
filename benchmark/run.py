#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs ONE cell of BENCHMARK.json once and prints, as the LAST line of
stdout, the contract's JSON object (correct, attempted, failed, metrics,
device, and with --trace 1 breakdown). Lines before it are for people.

It needs a TPU: without one, with fewer chips than the cell asks for, or on
a device_kind that benchmark/peaks.json does not list, it exits non-zero
and prints no result. --rehearsal runs the configuration's toy sizes on the
host cpu instead (virtual devices for a four-chip cell) to debug the
harness; its result line says "correct": false and "rehearsal": true, so it
can never be read as a chip run.

The harness is driven by data: the cell names a configuration
(benchmark/configs/<config>.json + .py) and a traffic mix
(benchmark/traffic/<mix>.json, which names its runner under
benchmark/runners/); each metric is read by a file of its own under
benchmark/end_to_end/ or benchmark/layer_metrics/. benchmark/README.md
says how to add one of each without editing a file that is there.
"""
import time
_T0 = time.perf_counter()       # process start, as near as python allows

import argparse
import gc
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _read_metrics(kind, package, cell_name, bench, run, say):
    out = {}
    for m in bench[kind]:
        if 'workloads' in m and cell_name not in m['workloads']:
            continue
        reader = importlib.import_module('benchmark.%s.%s'
                                         % (package, m['name']))
        value = reader.reduce(run)
        if value is None:        # nothing to read: left out of the line
            say('metric left out (its reader found nothing)', name=m['name'])
            continue
        out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def prepare(args):
    """Everything up to a live Context: the cell's files, the device rule,
    the compile cache. Returns the Context, or an exit code on refusal."""
    if not os.path.isdir(os.path.join(ROOT, 'paddle_tpu')):
        sys.stderr.write('benchmark: no paddle_tpu beside %s: nothing to '
                         'measure\n' % HERE)
        return 2
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness
    harness.T0 = _T0

    bench = harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    cells = {w['name']: w for w in bench['workloads']}
    if args.workload not in cells:
        sys.stderr.write('benchmark: no workload %r; have %s\n'
                         % (args.workload, sorted(cells)))
        return 2
    cell = cells[args.workload]
    cfg_entry = {c['name']: c for c in bench['configs']}[cell['config']]
    cfg_path = os.path.join(ROOT, cfg_entry['file'])
    cfg = harness.load_json(cfg_path)
    traffic = harness.load_json(os.path.join(
        HERE, 'traffic', cell['traffic'] + '.json'))
    seconds = args.seconds if args.seconds is not None \
        else float(bench['run_seconds'])
    chips = int(cell['chips'])

    if args.rehearsal:
        os.environ['JAX_PLATFORMS'] = 'cpu'
        os.environ['XLA_FLAGS'] = (
            os.environ.get('XLA_FLAGS', '')
            + ' --xla_force_host_platform_device_count=%d' % chips).strip()
        cfg = harness.overlay(cfg, cfg.get('rehearsal'))
        traffic = harness.overlay(traffic, traffic.get('rehearsal'))

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.stderr.write('benchmark: jax found no device: %s\n' % e)
        return 2
    want = 'cpu' if args.rehearsal else 'tpu'
    if devices[0].platform != want:
        sys.stderr.write('benchmark: needs a %s, jax found %s '
                         '(JAX_PLATFORMS=%r)\n'
                         % (want, sorted({d.platform for d in devices}),
                            os.environ.get('JAX_PLATFORMS')))
        return 2
    if len(devices) < chips:
        sys.stderr.write('benchmark: %s needs %d chip(s), jax found %d\n'
                         % (cell['name'], chips, len(devices)))
        return 2
    peaks_all = harness.load_json(os.path.join(HERE, 'peaks.json'))['devices']
    kind = devices[0].device_kind
    if args.rehearsal:
        peaks = next(iter(peaks_all.values()))   # arithmetic only runs
    elif kind not in peaks_all:
        sys.stderr.write('benchmark: no peaks on record for device_kind %r '
                         '— add it to benchmark/peaks.json with its source\n'
                         % kind)
        return 2
    else:
        peaks = peaks_all[kind]

    from paddle_tpu.core import compile_cache
    # the budget bounds what the package itself keeps on disk; the decode
    # artifact's executables are larger than the 512 MB default
    compile_cache.enable(max_mb=4096)

    ctx = harness.Context(bench, cell, cfg, cfg_path, traffic, args.seed,
                          seconds, args.trace, args.rehearsal)
    ctx.devices, ctx.peaks = devices, peaks
    ctx.cache_root = harness.cache_root()
    ctx.tracer = harness.Tracer(
        bool(args.trace), os.path.join(ctx.cache_root, 'benchmark_trace',
                                       cell['name']), ctx.spans)
    harness.say('cell %s on %d x %s (%s), seed %d, %.0f s, trace %d, '
                'cache %s' % (cell['name'], len(devices), kind,
                              devices[0].platform, args.seed, seconds,
                              args.trace, ctx.cache_root))
    return ctx


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=None)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--rehearsal', action='store_true',
                    help='toy sizes on the host cpu; never a chip result')
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    ctx = prepare(args)
    if isinstance(ctx, int):
        return ctx
    from benchmark import harness
    from paddle_tpu.core import compile_cache
    say = harness.say
    bench, cell, traffic, seconds = ctx.bench, ctx.cell, ctx.traffic, \
        ctx.seconds
    devices, kind = ctx.devices, ctx.devices[0].device_kind

    runner = importlib.import_module(
        'benchmark.runners.' + traffic['runner']).Runner(ctx)
    c0 = compile_cache.stats()
    try:
        runner.setup()
        c1 = compile_cache.stats()
        # what set-up built is not garbage: keep the collector from
        # walking it in the middle of the window (a full collection here
        # is tens of milliseconds, which is a whole decode tick)
        gc.collect()
        gc.freeze()
        result = runner.window(seconds)
        setup = {
            'setup_s': result['t_open'] - _T0,
            'setup_compiles_net':
                c1['xla_compiles_net'] - c0['xla_compiles_net'],
            'setup_exec_tier_hits': c1['exec_hits'] - c0['exec_hits'],
        }
        say('set-up', **setup)
        correct = bool(runner.verify())
    finally:
        runner.close()

    trace = None
    if args.trace:
        from benchmark import trace as trace_mod
        trace = trace_mod.load(ctx.tracer.path)
    run = {'ctx': ctx, 'runner': runner, 'result': result, 'setup': setup,
           'trace': trace}
    if args.trace:
        metrics = _read_metrics('per_layer', 'layer_metrics', cell['name'],
                                bench, run, say)
    else:
        metrics = _read_metrics('end_to_end', 'end_to_end', cell['name'],
                                bench, run, say)
    device = {'platform': devices[0].platform, 'kind': kind,
              'count': len(devices),
              'memory_peak_bytes': harness.memory_peak_bytes(devices)}
    line = {'correct': correct and not args.rehearsal,
            'attempted': int(result['attempted']),
            'failed': int(result['failed']),
            'metrics': metrics, 'device': device}
    if trace is not None:
        device['busy_s'] = trace_mod.mean_busy_seconds(trace)
        device['window_s'] = trace_mod.window_seconds(trace)
        line['breakdown'] = {
            'device_ops': trace_mod.top_ops(trace, 10),
            'idle_gaps': trace_mod.idle_by_host_activity(trace, 10)}
        say('device idle share', percent=100.0 * (
            1 - device['busy_s'] / device['window_s']))
    if args.rehearsal:
        line['rehearsal'] = True
        line['rehearsal_checks_passed'] = correct
    for name, m in sorted(metrics.items()):
        say('  %s = %.6g %s' % (name, m['value'], m['unit']))
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
