"""Profiler surface (ref: python/paddle/fluid/profiler.py,
platform/profiler.cc event tables, tools/timeline.py Chrome export).

TPU-native split of responsibilities:
- DEVICE time: the whole step is one XLA program; jax.profiler traces
  capture per-kernel spans for TensorBoard/Perfetto (subsuming the
  reference's CUPTI DeviceTracer).
- HOST time: `span(name, **stats)` (reference name: `record_event`) is
  the ONE span primitive of the program — the executor, the compile
  cache, the passes and (through inference/serve.py) the decode scheduler
  all use it. It is a jax.profiler.TraceAnnotation, so it lands on the
  device trace's own clock in ANY running jax profiler trace, whoever
  started it, and is inert otherwise. Under start_profiler/profiler() the
  spans also aggregate into the reference's min/max/avg/total report at
  stop_profiler, and export to Chrome tracing JSON via
  `export_chrome_tracing` — the tools/timeline.py capability without the
  proto intermediary.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

_trace_dir = None
_events = []            # (name, start_s, dur_s, tid)
_active = False
# single consistent epoch for every event timestamp (chrome traces need
# one time base regardless of when profiling starts)
_EPOCH = time.perf_counter()


def is_profiling():
    return _active


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    yield  # CUDA-specific; no-op on TPU


def start_profiler(state='All', tracer_option=None):
    global _trace_dir, _active
    import jax
    _trace_dir = os.environ.get('PTPU_PROFILE_DIR', '/tmp/paddle_tpu_profile')
    os.makedirs(_trace_dir, exist_ok=True)
    # hook the compile-event counter (and its compile source) even when
    # the persistent cache is off, so stop_profiler can report per-run
    # compile events whenever any compile occurred
    try:
        from .core import compile_cache
        compile_cache._ensure_listener()
        compile_cache._register_profiler_source()
    except Exception:
        pass
    jax.profiler.start_trace(_trace_dir)
    _active = True


def stop_profiler(sorted_key=None, profile_path='/tmp/profile'):
    global _active
    import jax
    jax.profiler.stop_trace()
    _active = False
    _print_report(sorted_key)
    if _serving_sources:
        serving_report()
    if _fleet_sources:
        fleet_report()
    if _gateway_sources:
        gateway_report()
    if _training_sources:
        training_report()   # renders feeder + pod sources too
    else:
        if _feeder_sources:
            feeder_report()
        if _pod_sources:
            pod_report()
    if _infer_sources:
        infer_report()
    if _compile_sources:
        compile_report()
    print("[paddle_tpu.profiler] device trace written to %s "
          "(open with TensorBoard / Perfetto); host events: "
          "export_chrome_tracing(path)" % _trace_dir)


def _print_report(sorted_key=None):
    """Aggregate host events like the reference's profiler report
    (platform/profiler.cc PrintProfiler: calls/total/min/max/avg)."""
    agg = {}
    for name, _start, dur, _tid in _events:
        a = agg.setdefault(name, [0, 0.0, float('inf'), 0.0])
        a[0] += 1
        a[1] += dur
        a[2] = min(a[2], dur)
        a[3] = max(a[3], dur)
    if not agg:
        return
    rows = sorted(agg.items(), key=lambda kv: kv[1][1], reverse=True)
    if sorted_key == 'calls':
        rows = sorted(agg.items(), key=lambda kv: kv[1][0], reverse=True)
    print("%-40s %8s %12s %12s %12s %12s" %
          ('Event', 'Calls', 'Total(ms)', 'Min(ms)', 'Max(ms)', 'Avg(ms)'))
    for name, (calls, total, mn, mx) in rows:
        print("%-40s %8d %12.3f %12.3f %12.3f %12.3f" %
              (name[:40], calls, total * 1e3, mn * 1e3, mx * 1e3,
               total * 1e3 / calls))


def export_chrome_tracing(path):
    """Write recorded host events as Chrome tracing JSON
    (chrome://tracing / Perfetto; ref tools/timeline.py:115)."""
    trace = {'traceEvents': [
        {'name': name, 'ph': 'X', 'pid': 0, 'tid': tid,
         'ts': start * 1e6, 'dur': dur * 1e6, 'cat': 'host'}
        for name, start, dur, tid in _events]}
    with open(path, 'w') as f:
        json.dump(trace, f)
    return path


def reset_profiler():
    global _events
    _events = []


# -- serving metrics ---------------------------------------------------------
# Dynamic-batching predictors (inference/batching.py) register a zero-arg
# snapshot callable here; serving_report() renders the queue depth, batch
# occupancy, and request-latency percentiles per live source, and
# stop_profiler appends the same table to the host-event report.
_serving_sources = {}


def register_serving_source(name, snapshot):
    """Register a serving-metrics source: `snapshot()` -> dict with
    queue_depth, requests, batches, occupancy, p50/p95/p99_ms (the
    contract of batching.ServingStats.snapshot)."""
    _serving_sources[name] = snapshot


def unregister_serving_source(name):
    _serving_sources.pop(name, None)


def serving_report():
    """Print serving metrics for every registered source and return them
    as {source name: snapshot dict}. Decode-serving sources (snapshots
    with kind='decode': inference/decoding.DecodingPredictor) render in
    their own table — tokens/s, slot occupancy, prefill/decode dispatch
    split, TTFT and inter-token latency percentiles — next to the
    request-batching table. Block-paged sources (ISSUE 13: snapshots
    carrying blocks_in_use) grow block-cache columns: blocks in use /
    total, prefix-share hit rate, copy-on-write block copies, and
    chunked-prefill slices — the capacity-vs-sharing picture per
    replica. The speculative-decode columns (ISSUE 17) render for every
    decode source: `acc` is the draft acceptance rate and `tok/d` the
    tokens delivered per request-advancing dispatch — both identically
    1.00 for plain (non-drafting) decode, so mixed spec/non-spec fleets
    line up in one table. The tick columns come from the source's tick
    log (`DecodeStats.tick_log`), its last 8,192 ticks: a scheduler
    tick's p50 / p99 / longest wall time and `offcpu`, the share of the
    ticks' time the scheduler's thread was neither on a CPU nor waiting
    for the device; `gapp50` / `gapp99` the gap between two steps'
    deliveries, counted once a row delivered to — what a decoding stream
    feels of the tick, which a tick's wait for a prompt's last slice is
    not in. The last six columns part the time to a first token over the
    source's request log (`DecodeStats.request_log`), its last 8,192
    requests that ended: queued (`queue`), from admission to the dispatch
    of the prompt's last slice (`pfill`), from there to the token
    delivered (`read`), p50 and p99 each."""
    out = {}
    rows = []
    decode_rows = []
    for name in sorted(_serving_sources):
        try:
            snap = _serving_sources[name]()
        except Exception:
            continue  # a closing batcher must not break the report
        out[name] = snap
        if snap.get('kind') == 'decode':
            decode_rows.append((name, snap))
        else:
            rows.append((name, snap))
    if rows:
        # tier column (ISSUE 11): bf16/int8 per source, so a fleet
        # serving mixed artifact tiers is auditable in one table
        print("%-32s %5s %6s %8s %8s %5s %7s %7s %9s %9s %9s" %
              ('Serving source', 'tier', 'queue', 'requests', 'batches',
               'occ', 'shed', 'expired', 'p50(ms)', 'p95(ms)',
               'p99(ms)'))
        for name, s in rows:
            print("%-32s %5s %6d %8d %8d %5.2f %7d %7d %9.2f %9.2f "
                  "%9.2f" %
                  (name[:32], s.get('tier', 'bf16'),
                   s.get('queue_depth', 0),
                   s.get('requests', 0), s.get('batches', 0),
                   s.get('occupancy', 0.0), s.get('shed', 0),
                   s.get('expired', 0), s.get('p50_ms', 0.0),
                   s.get('p95_ms', 0.0), s.get('p99_ms', 0.0)))
    if decode_rows:
        # block-cache columns render only when some source reports its
        # pool (a DecodeStats not yet wired to one does not)
        blocks = any('blocks_in_use' in s for _, s in decode_rows)
        hdr = ("%-26s %5s %5s %6s %7s %8s %8s %6s %5s %5s %5s %6s %10s "
               "%10s %9s %9s" %
               ('Decode source', 'tier', 'queue', 'reqs', 'tokens',
                'tok/s', 'prefills', 'steps', 'occ', 'shed',
                'acc', 'tok/d',
                'ttftp50(ms)', 'ttftp99(ms)', 'itlp50(ms)', 'itlp99(ms)'))
        # the scheduler's tick over its tick log: wall time, and the
        # share of it its thread neither ran nor waited for the device
        hdr += " %11s %11s %11s %6s" % ('tickp50(ms)', 'tickp99(ms)',
                                        'tickmax(ms)', 'offcpu')
        # what a decoding stream feels of the tick, and where a first
        # token's time went: queue, prefill, the last slice's read
        hdr += " %10s %10s" % ('gapp50(ms)', 'gapp99(ms)')
        hdr += " %8s %8s %8s %8s %8s %8s" % (
            'queuep50', 'queuep99', 'pfillp50', 'pfillp99', 'readp50',
            'readp99')
        if blocks:
            # prefill slices, the chunk-program calls that carried them
            # (fewer where slices rode the row program together) and the
            # ticks a due slice waited for room in a tick's prefill budget;
            # the slices that started past their prompt's first token
            hdr += " %11s %6s %6s %6s %6s %6s %7s" % (
                'blocks', 'pfxhit', 'cow', 'slices', 'calls', 'waits',
                'carried')
            # bytes a cached position takes over all layers, and the
            # pools' bytes by kind (kv, latent, window, recurrent)
            hdr += " %7s %s" % ('row(B)', 'pools(MB)')
        print(hdr)
        for name, s in decode_rows:
            row = ("%-26s %5s %5d %6d %7d %8.1f %8d %6d %5.2f %5d %5.2f "
                   "%6.2f %10.2f %10.2f %9.2f %9.2f" %
                   (name[:26], s.get('tier', 'bf16'),
                    s.get('queue_depth', 0),
                    s.get('requests', 0), s.get('tokens', 0),
                    s.get('tokens_s', 0.0), s.get('prefills', 0),
                    s.get('steps', 0), s.get('occupancy', 0.0),
                    s.get('shed', 0) + s.get('expired', 0),
                    s.get('acc_rate', 1.0),
                    s.get('tokens_per_dispatch', 1.0),
                    s.get('ttft_p50_ms', 0.0), s.get('ttft_p99_ms', 0.0),
                    s.get('itl_p50_ms', 0.0), s.get('itl_p99_ms', 0.0)))
            row += " %11.2f %11.2f %11.2f %6.2f" % (
                s.get('tick_p50_ms', 0.0), s.get('tick_p99_ms', 0.0),
                s.get('tick_max_ms', 0.0), s.get('tick_offcpu_share', 0.0))
            row += " %10.2f %10.2f" % (s.get('emit_gap_p50_ms', 0.0),
                                       s.get('emit_gap_p99_ms', 0.0))
            row += " %8.2f %8.2f %8.2f %8.2f %8.2f %8.2f" % tuple(
                s.get('ttft_%s_p%d_ms' % (part, q), 0.0)
                for part in ('queue', 'prefill', 'read') for q in (50, 99))
            if blocks:
                if 'blocks_in_use' in s:
                    row += " %11s %6.2f %6d %6d %6d %6d %7d" % (
                        '%d/%d' % (s['blocks_in_use'],
                                   s.get('blocks_total', 0)),
                        s.get('prefix_hit_rate', 0.0),
                        s.get('cow_blocks', 0),
                        s.get('chunk_slices', 0),
                        s.get('chunk_dispatches', 0),
                        s.get('slices_deferred', 0),
                        s.get('slices_carried', 0))
                    row += " %7d %s" % (
                        s.get('cache_row_bytes', 0),
                        ' '.join('%s:%.0f' % (k, v / 1e6) for k, v in
                                 sorted(s.get('pool_bytes', {}).items())))
                else:
                    row += " %11s %6s %6s %6s %6s %6s %7s" % (('-',) * 7)
            print(row)
    return out


# -- serving-fleet metrics ---------------------------------------------------
# Fleet routers (inference/fleet.FleetRouter) register a zero-arg snapshot
# callable here; fleet_report() renders one summary row per fleet (requests,
# reroutes, sheds, latency/TTFT percentiles, scale events, rollout state)
# plus a per-replica table (state, tier, outstanding+queued work, replica
# occupancy, heartbeat age), alongside the serving tables at stop_profiler.
_fleet_sources = {}


def register_fleet_source(name, snapshot):
    """Register a fleet-metrics source: `snapshot()` -> dict with
    serving, replicas={rid: replica snapshot}, completed, failed,
    rerouted, shed, expired, p50/p99_ms, ttft_p50/p99_ms, scale_out,
    scale_in, replica_deaths, rollout (the contract of
    fleet.FleetRouter.fleet_snapshot)."""
    _fleet_sources[name] = snapshot


def unregister_fleet_source(name):
    _fleet_sources.pop(name, None)


def fleet_report():
    """Print fleet metrics for every registered source and return them
    as {source name: snapshot dict}."""
    out = {}
    rows = []
    for name in sorted(_fleet_sources):
        try:
            snap = _fleet_sources[name]()
        except Exception:
            continue  # a closing router must not break the report
        out[name] = snap
        rows.append((name, snap))
    if rows:
        print("%-28s %5s %7s %8s %6s %7s %5s %9s %9s %11s %7s %8s" %
              ('Fleet source', 'tier', 'serving', 'requests', 'fail',
               'reroute', 'shed', 'p50(ms)', 'p99(ms)', 'ttft99(ms)',
               'scale', 'rollout'))
    for name, snap in rows:
        print("%-28s %5s %7d %8d %6d %7d %5d %9.2f %9.2f %11.2f %3d/%-3d "
              "%8s" %
              (name[:28], snap.get('tier', 'bf16'),
               snap.get('serving', 0), snap.get('completed', 0),
               snap.get('failed', 0), snap.get('rerouted', 0),
               snap.get('shed', 0) + snap.get('expired', 0),
               snap.get('p50_ms', 0.0), snap.get('p99_ms', 0.0),
               snap.get('ttft_p99_ms', 0.0), snap.get('scale_out', 0),
               snap.get('scale_in', 0),
               snap.get('rollout', {}).get('state', 'idle')[:8]))
        replicas = snap.get('replicas', {})
        if replicas:
            print("  %-8s %-9s %5s %8s %8s %5s %9s %8s %8s" %
                  ('replica', 'state', 'tier', 'backlog', 'requests',
                   'occ', 'hb-age(s)', 'spinup(s)', 'compiles'))
            for rid in sorted(replicas, key=lambda r: int(r)):
                s = replicas[rid]
                age = s.get('hb_age_s')
                # backlog = router pending + worker queue (a dispatched
                # frame is already in the worker's queue_depth; adding
                # outstanding would double-count it)
                print("  %-8s %-9s %5s %8d %8d %5.2f %9s %8s %8s" %
                      (rid, s.get('state', '?')[:9],
                       s.get('tier', 'bf16'),
                       s.get('pending', 0) + s.get('queue_depth', 0),
                       s.get('requests', 0), s.get('occupancy', 0.0),
                       ('%.2f' % age) if age is not None else '-',
                       ('%.2f' % s['spinup_s'])
                       if s.get('spinup_s') is not None else '-',
                       s.get('compiles') if s.get('compiles')
                       is not None else '-'))
    return out


# -- serving-gateway metrics -------------------------------------------------
# HTTP gateways (inference/gateway.Gateway) register a zero-arg snapshot
# callable here; gateway_report() renders one summary row per gateway
# (requests by outcome, inflight, TTFB/TTFT percentiles, drain state)
# plus a per-tenant admission table (requests, rate-limited, quota and
# overload sheds, expiries), alongside the fleet table at stop_profiler.
_gateway_sources = {}


def register_gateway_source(name, snapshot):
    """Register a gateway-metrics source: `snapshot()` -> dict with
    requests, ok, rate_limited, quota, shed, expired, failed, inflight,
    streams, draining, ttfb/ttft percentiles, tenants={name: tenant
    counters} (the contract of gateway.Gateway.snapshot)."""
    _gateway_sources[name] = snapshot


def unregister_gateway_source(name):
    _gateway_sources.pop(name, None)


def gateway_report():
    """Print gateway metrics for every registered source and return
    them as {source name: snapshot dict}."""
    out = {}
    rows = []
    for name in sorted(_gateway_sources):
        try:
            snap = _gateway_sources[name]()
        except Exception:
            continue  # a closing gateway must not break the report
        out[name] = snap
        rows.append((name, snap))
    if rows:
        print("%-30s %8s %8s %5s %6s %5s %7s %6s %8s %10s %10s %6s" %
              ('Gateway source', 'requests', 'ok', '429', 'quota',
               'shed', 'expired', 'fail', 'inflight', 'ttfb99(ms)',
               'ttft99(ms)', 'drain'))
    for name, snap in rows:
        print("%-30s %8d %8d %5d %6d %5d %7d %6d %8d %10.2f %10.2f "
              "%6s" %
              (name[:30], snap.get('requests', 0), snap.get('ok', 0),
               snap.get('rate_limited', 0), snap.get('quota', 0),
               snap.get('shed', 0), snap.get('expired', 0),
               snap.get('failed', 0), snap.get('inflight', 0),
               snap.get('ttfb_p99_ms', 0.0),
               snap.get('ttft_p99_ms', 0.0),
               'yes' if snap.get('draining') else 'no'))
        tenants = snap.get('tenants', {})
        if tenants:
            print("  %-20s %8s %8s %5s %6s %5s %7s %6s %8s" %
                  ('tenant', 'requests', 'ok', '429', 'quota', 'shed',
                   'expired', 'fail', 'inflight'))
            for tname in sorted(tenants):
                t = tenants[tname]
                print("  %-20s %8d %8d %5d %6d %5d %7d %6d %8d" %
                      (tname[:20], t.get('requests', 0), t.get('ok', 0),
                       t.get('rate_limited', 0), t.get('quota', 0),
                       t.get('shed', 0), t.get('expired', 0),
                       t.get('failed', 0), t.get('inflight', 0)))
    return out


# -- multi-step training dispatch metrics ------------------------------------
# Executors running run_steps (multi-step dispatch, ISSUE 2) register a
# zero-arg snapshot callable here; training_report() renders per-dispatch
# step counts, EOF tail flushes, and host-stall time (waiting on the
# prefetch ring), and stop_profiler appends the same table to the report.
_training_sources = {}


def register_training_source(name, snapshot):
    """Register a multi-step-dispatch metrics source: `snapshot()` -> dict
    with dispatches, steps, steps_per_dispatch, tail_flushes,
    host_stall_ms (the contract of Executor.run_steps' counters)."""
    _training_sources[name] = snapshot


def unregister_training_source(name):
    _training_sources.pop(name, None)


def training_report():
    """Print multi-step training dispatch metrics for every registered
    source and return them as {source name: snapshot dict}. stall% is
    the share of run_steps wall time spent WAITING for input (the
    feeder-saturation headline: the data plane's job is driving it to
    ~0). When feeder sources are registered (sharded/pooled readers,
    reader/sharded.py), their table renders right below — decode time,
    queue depth, worker occupancy — so a stall reads straight across to
    its cause."""
    out = {}
    rows = []
    for name in sorted(_training_sources):
        try:
            snap = _training_sources[name]()
        except Exception:
            continue  # a closing executor must not break the report
        out[name] = snap
        rows.append((name, snap))
    if rows:
        print("%-32s %10s %8s %10s %6s %12s %7s %9s %6s" %
              ('Training source', 'dispatches', 'steps', 'steps/disp',
               'tails', 'stall(ms)', 'stall%', 'ckpt(ms)', 'ckpt%'))
        for name, s in rows:
            print("%-32s %10d %8d %10.2f %6d %12.2f %7.2f %9.2f %6.2f" %
                  (name[:32], s.get('dispatches', 0), s.get('steps', 0),
                   s.get('steps_per_dispatch', 0.0),
                   s.get('tail_flushes', 0), s.get('host_stall_ms', 0.0),
                   s.get('host_stall_pct', 0.0),
                   s.get('ckpt_stall_ms', 0.0),
                   s.get('ckpt_stall_pct', 0.0)))
    if _feeder_sources:
        out['feeders'] = feeder_report()
    if _pod_sources:
        out['pod'] = pod_report()
    return out


# -- pod health metrics ------------------------------------------------------
# Pod checkpoint managers (core/checkpoint.PodCheckpointManager) register a
# zero-arg snapshot callable here; pod_report() renders one row per pod
# HOST — training step, heartbeat age, checkpoint stall, barrier wait,
# commit/abandon counters — read from the shared heartbeat files, so ONE
# process prints the health of the whole pod. training_report() appends the
# same table so a stall reads straight across to the host causing it.
_pod_sources = {}


def register_pod_source(name, snapshot):
    """Register a pod-health source: `snapshot()` -> dict with num_hosts,
    rank, and hosts={rank: heartbeat payload + age_s} (the contract of
    PodCheckpointManager's heartbeat files)."""
    _pod_sources[name] = snapshot


def unregister_pod_source(name):
    _pod_sources.pop(name, None)


def pod_report(stale_after_s=10.0):
    """Print per-host pod health for every registered source and return
    {source name: snapshot dict}. `alive` is heartbeat-age-based
    (stale_after_s), the same bounded-time signal HostWatchdog acts on."""
    out = {}
    for name in sorted(_pod_sources):
        try:
            snap = _pod_sources[name]()
        except Exception:
            continue  # a closed manager must not break the report
        out[name] = snap
        hosts = snap.get('hosts', {})
        if not hosts:
            continue
        print("%-24s %5s %6s %-16s %10s %10s %6s %12s %8s %10s %6s" %
              ('Pod source', 'host', 'step', 'topology', 'hb-age(s)',
               'ckpt(ms)', 'ckpt%', 'barrier(ms)', 'commits', 'abandoned',
               'alive'))
        for rank in sorted(hosts):
            h = hosts[rank]
            age = h.get('age_s', float('inf'))
            # topology (hosts x mesh axes) makes an elastic resize
            # visible here: the new incarnation's heartbeats carry the
            # NEW shape; stale-shape files from the old incarnation are
            # ignored upstream by run_id/num_hosts
            print("%-24s %5d %6d %-16s %10.2f %10.2f %6.2f %12.2f %8d "
                  "%10d %6s" %
                  (name[:24], rank, h.get('step', 0),
                   str(h.get('topology', '-'))[:16], age,
                   h.get('ckpt_stall_ms', 0.0),
                   h.get('ckpt_stall_pct', 0.0),
                   h.get('barrier_ms', 0.0), h.get('commits', 0),
                   h.get('pod_abandoned', 0),
                   'yes' if age <= stale_after_s else 'NO'))
    return out


# -- feeder / data-plane metrics ---------------------------------------------
# Input-pipeline sources (reader/pipeline.PyReader over a pooled/sharded
# reader, reader/sharded.FeederStats) register a zero-arg snapshot callable
# here; feeder_report() renders per-source decode time, queue depth, worker
# occupancy, deaths/retries, and ring staging time, and training_report()
# appends the same table so host-stall and its feeder-side cause print
# together.
_feeder_sources = {}


def register_feeder_source(name, snapshot):
    """Register a feeder-metrics source: `snapshot()` -> dict with
    samples, decode_ms_avg, queue_depth, occupancy, workers,
    workers_live, deaths, retries, and optionally stage_ms/ring_depth/
    convert_ms (the contract of sharded.FeederStats.snapshot plus
    PyReader's ring counters)."""
    _feeder_sources[name] = snapshot


def unregister_feeder_source(name):
    _feeder_sources.pop(name, None)


def feeder_report():
    """Print feeder/data-plane metrics for every registered source and
    return them as {source name: snapshot dict}."""
    out = {}
    rows = []
    for name in sorted(_feeder_sources):
        try:
            snap = _feeder_sources[name]()
        except Exception:
            continue  # a collected reader must not break the report
        out[name] = snap
        rows.append((name, snap))
    if rows:
        print("%-26s %8s %9s %6s %5s %8s %7s %8s %10s %9s" %
              ('Feeder source', 'samples', 'dec(ms)', 'queue', 'occ',
               'workers', 'deaths', 'retries', 'stage(ms)', 'conv(ms)'))
        for name, s in rows:
            workers = s.get('workers')
            wl = s.get('workers_live', workers)
            print("%-26s %8d %9.3f %6d %5.2f %8s %7d %8d %10.2f %9.2f" %
                  (name[:26], s.get('samples', 0),
                   s.get('decode_ms_avg', 0.0),
                   s.get('queue_depth', s.get('ring_depth', 0)),
                   s.get('occupancy', 0.0),
                   ('%d/%d' % (wl, workers)) if workers else '-',
                   s.get('deaths', 0), s.get('retries', 0),
                   s.get('stage_ms', 0.0), s.get('convert_ms', 0.0)))
    return out


# -- bulk-inference dispatch metrics -----------------------------------------
# Bulk-inference loops (serve.CompiledPredictor.run_batches, and Executors
# driving Predictor.run_batches) register a zero-arg snapshot callable
# here; infer_report() renders per-dispatch batch counts, tail flushes,
# host staging time, and device occupancy (device-call share of the bulk
# call's wall time — absent for async executor-side sources), and
# stop_profiler appends the same table to the report.
_infer_sources = {}


def register_infer_source(name, snapshot):
    """Register a bulk-inference metrics source: `snapshot()` -> dict with
    dispatches, batches, batches_per_dispatch, tail_flushes,
    host_stall_ms, and optionally occupancy (the contract of
    serve.CompiledPredictor.bulk_stats)."""
    _infer_sources[name] = snapshot


def unregister_infer_source(name):
    _infer_sources.pop(name, None)


def infer_report():
    """Print bulk-inference dispatch metrics for every registered source
    and return them as {source name: snapshot dict}."""
    out = {}
    rows = []
    for name in sorted(_infer_sources):
        try:
            snap = _infer_sources[name]()
        except Exception:
            continue  # a collected predictor must not break the report
        out[name] = snap
        rows.append((name, snap))
    if rows:
        print("%-32s %10s %8s %10s %6s %10s %5s" %
              ('Bulk-infer source', 'dispatches', 'batches', 'batch/disp',
               'tails', 'stage(ms)', 'occ'))
        for name, s in rows:
            occ = s.get('occupancy')
            print("%-32s %10d %8d %10.2f %6d %10.2f %5s" %
                  (name[:32], s.get('dispatches', 0), s.get('batches', 0),
                   s.get('batches_per_dispatch', 0.0),
                   s.get('tail_flushes', 0), s.get('host_stall_ms', 0.0),
                   ('%.2f' % occ) if occ is not None else '-'))
    return out


# -- compile / compile-cache metrics -----------------------------------------
# The persistent compile cache (core/compile_cache.py) registers a zero-arg
# snapshot callable here; compile_report() renders per-run compile events —
# XLA compiles performed, seconds spent, cache hits per tier, bytes moved —
# and stop_profiler appends the same table whenever any compile (or cache
# traffic) occurred during the run.
_compile_sources = {}


def register_compile_source(name, snapshot):
    """Register a compile-metrics source: `snapshot()` -> dict with
    compiles, compile_s, exec_hits, hlo_hits, misses, bytes_read,
    bytes_written, xla_compiles, xla_compiles_net (the contract of
    core.compile_cache.stats)."""
    _compile_sources[name] = snapshot


def unregister_compile_source(name):
    _compile_sources.pop(name, None)


def compile_report():
    """Print compile/cache metrics for every registered source and return
    them as {source name: snapshot dict}. Sources with no compile AND no
    cache traffic are skipped — the table only appears when something
    compiled or warm-started."""
    out = {}
    rows = []
    for name in sorted(_compile_sources):
        try:
            snap = _compile_sources[name]()
        except Exception:
            continue  # a torn-down cache must not break the report
        out[name] = snap
        if (snap.get('xla_compiles', 0) or snap.get('compiles', 0)
                or snap.get('exec_hits', 0) or snap.get('hlo_hits', 0)
                or snap.get('misses', 0)):
            rows.append((name, snap))
    if rows:
        print("%-20s %8s %10s %6s %6s %6s %9s %8s %10s %10s" %
              ('Compile source', 'compiles', 'xla(net)', 'exec+', 'hlo+',
               'miss', 'cache(s)', 'xla(s)', 'read(B)', 'written(B)'))
        for name, s in rows:
            print("%-20s %8d %10d %6d %6d %6d %9.2f %8.2f %10d %10d" %
                  (name[:20], s.get('compiles', 0),
                   s.get('xla_compiles_net', s.get('xla_compiles', 0)),
                   s.get('exec_hits', 0), s.get('hlo_hits', 0),
                   s.get('misses', 0), s.get('compile_s', 0.0),
                   s.get('xla_compile_s', 0.0),
                   s.get('bytes_read', 0), s.get('bytes_written', 0)))
    return out


@contextlib.contextmanager
def profiler(state='All', sorted_key=None, profile_path='/tmp/profile',
             tracer_option=None):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class span(_TraceAnnotation):
    """`with span('<layer>/<what>', **stats):` — a named interval on the
    calling thread, in whatever jax profiler trace is running (ref
    platform::RecordEvent). Names carry no ids: those go in `stats`,
    plain ints/strs already at hand; a stat known only once the work is
    done is added inside the block with `.set_metadata(k=v)`. With no
    trace running it costs about a microsecond and records nothing.
    While a trace runs every span also carries `cpu_us`, the CPU time
    its thread used inside it (CLOCK_THREAD_CPUTIME_ID): its wall time
    minus that is the time the thread was NOT running — waiting for the
    GIL, a lock, the run queue or a blocking runtime call. A holder's
    `cpu_us` includes its children's; a reader subtracts them. The clock
    is the kernel's: where it moves in steps (10 ms on some machines) one
    span's `cpu_us` is a sample, and sums over many spans are the reading.
    While `is_profiling()` it also keeps (name, start, dur, tid) for the
    host-event report and `export_chrome_tracing`."""

    def __init__(self, name, **stats):
        super().__init__(name, **stats)
        self._name = name
        self._t0 = None
        self._cpu0 = None

    def __enter__(self):
        if _active:
            self._t0 = time.perf_counter()
        if _TraceAnnotation.is_enabled():
            self._cpu0 = time.thread_time_ns()
        return super().__enter__()

    def __exit__(self, *exc):
        if self._cpu0 is not None:
            self.set_metadata(
                cpu_us=(time.thread_time_ns() - self._cpu0) / 1e3)
        super().__exit__(*exc)
        if self._t0 is not None:
            _events.append((self._name, self._t0 - _EPOCH,
                            time.perf_counter() - self._t0,
                            threading.get_ident() % 10000))
        return False


record_event = span     # the reference API's name for the same thing
