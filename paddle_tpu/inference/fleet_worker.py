"""One fleet replica subprocess (ISSUE 12).

Spawned by `fleet.FleetRouter`:

    python fleet_worker.py SOCKET_PATH REPLICA_ID ARTIFACT_DIR \
                           HEARTBEAT_PATH OPTS_JSON

Loads the artifact FRAMEWORK-FREE (file-path imports of the sibling
serving modules; with AOT sidecars present the spin-up performs zero
XLA compiles — the count is reported in the hello frame), serves
requests over fleet.py's length-prefixed frame protocol, and writes a
heartbeat file (atomic replace; mtime = liveness, payload = serving
stats) on an interval — the round-13 liveness pattern the router's
watchdog reads. A SIGSTOP'd (hung) worker stops heartbeating and is
detected in bounded time; a SIGKILL'd one drops the socket.

OPTS keys: kind ('batching'|'decoding'|'compiled'), tier, platform,
warmup, hb_interval_s, max_queue, batch_timeout_ms, max_batch_size,
inflight, default_max_new.

Frames handled: infer / decode (per-request), drain (predictor drain()
hook: stop admitting, finish in-flight, shed the queue re-routably),
stop. Replies: result (ok or etype/error/requeue), tok (greedy decode
streaming), drained, bye. The hello frame carries the artifact tier the
endpoint ACTUALLY serves plus — for decode artifacts — the mesh tag
('cpu_mp2', None unsharded), so mp-sharded decode tiers (ISSUE 13) route
through the same protocol with the router able to audit what each
replica loaded.
"""
import json
import os
import socket
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import fleet as _fleet  # noqa: E402
import serve as _serve  # noqa: E402
import batching as _batching  # noqa: E402
import decoding as _decoding  # noqa: E402


class _Conn(object):
    """Socket with a send lock: results/toks/heartbeats come from
    predictor callback threads concurrently."""

    def __init__(self, sock):
        self.sock = sock
        self.lock = threading.Lock()

    def send(self, header, arrays=None):
        with self.lock:
            _fleet._send_frame(self.sock, header, arrays)

    def reply_err(self, req_id, exc, requeue=False):
        self.send({'op': 'result', 'id': req_id, 'ok': False,
                   'etype': type(exc).__name__, 'error': str(exc),
                   'requeue': bool(requeue)})


def _is_requeueable(exc, draining):
    """SUBMIT-SITE only: shed-at-the-door errors never cost device work
    — the router can safely re-route them; a draining/closed refusal
    raised by submit() itself is the same no-work case. Errors from a
    request that already DISPATCHED (delivery callbacks, stream pumps)
    must use _stream_requeueable instead — a mid-execution error may
    have cost device work and the fleet contract forbids blind retries
    of those."""
    return isinstance(exc, _batching.ServerOverloaded) or (
        draining and isinstance(exc, RuntimeError))


def _stream_requeueable(exc):
    """POST-DISPATCH (stream pump / delivery callback) re-route
    decision: only a shed that provably cost no device work may
    re-route. MidStreamEvicted is a ServerOverloaded whose victim
    already streamed tokens — re-routing would replay them to the
    client and blindly retry device work."""
    return (isinstance(exc, _batching.ServerOverloaded)
            and not isinstance(exc, _decoding.MidStreamEvicted))


class _BatchingEndpoint(object):
    kind = 'batching'

    def __init__(self, artifact, opts):
        kw = {}
        for k in ('tier', 'platform', 'max_queue', 'max_batch_size'):
            if opts.get(k) is not None:
                kw[k] = opts[k]
        kw['batch_timeout_ms'] = float(opts.get('batch_timeout_ms', 2.0))
        kw['inflight'] = int(opts.get('inflight', 2))
        self.pred = _batching.BatchingPredictor(artifact, **kw)
        if opts.get('warmup', True):
            self.pred.warmup()
        self.tier = self.pred.tier
        self._levels = [int(e.get('lod_levels', 0)) for e in
                        _serve._fetch_entries(self.pred._sig)]
        self.draining = False

    def submit(self, hdr, arrays, conn):
        req_id = hdr['id']
        lod_keys = [k for k in arrays if '.lod' in k]
        if lod_keys:
            # the batcher serves dense feeds only (its own load-time
            # contract): dropping offsets silently could return wrong
            # results — fail THIS request loudly instead
            conn.reply_err(req_id, ValueError(
                'batching fleet serves dense feeds only; request '
                'carries lod offsets %r — serve LoD artifacts with '
                "kind='compiled'" % lod_keys))
            return
        feed = dict(arrays)

        def _done(fut):
            exc = fut.exception()
            if exc is not None:
                # post-submit resolution: only a genuine shed (never
                # dispatched) is safe to re-route
                conn.reply_err(req_id, exc, _stream_requeueable(exc))
                return
            outs = fut.result()
            conn.send({'op': 'result', 'id': req_id, 'ok': True,
                       'n': len(outs), 'lod': self._levels},
                      {'o%d' % j: o for j, o in enumerate(outs)})
        try:
            fut = self.pred.submit(feed,
                                   deadline_ms=hdr.get('deadline_ms'),
                                   request_id=hdr.get('request_id'))
        except Exception as e:
            conn.reply_err(req_id, e,
                           _is_requeueable(e, self.draining))
            return
        fut.add_done_callback(_done)

    def drain(self):
        self.draining = True
        self.pred.drain()

    def snapshot(self):
        return self.pred.stats.snapshot()

    def close(self):
        self.pred.close()


class _DecodingEndpoint(object):
    kind = 'decoding'

    def __init__(self, artifact, opts):
        kw = {}
        # 'draft' (ISSUE 17): 'ngram' attaches the host-side prompt-
        # lookup drafter — the only drafter expressible in a spawn
        # config; 'draft_k' narrows the per-tick draft length
        for k in ('tier', 'platform', 'max_queue', 'draft', 'draft_k'):
            if opts.get(k) is not None:
                kw[k] = opts[k]
        if opts.get('default_max_new') is not None:
            kw['default_max_new_tokens'] = int(opts['default_max_new'])
        self.pred = _decoding.DecodingPredictor(artifact, **kw)
        if opts.get('warmup', True):
            self.pred.warmup()
        self.tier = self.pred.stats.tier
        # ISSUE 13: mp-sharded decode artifacts load through the same
        # endpoint (DecodingPredictor reads the mesh from the
        # signature); surface it so the router and fleet_ctl can audit
        # which tier a replica actually serves
        self.mesh = self.pred.mesh_tag
        self.draining = False

    def submit(self, hdr, arrays, conn):
        req_id = hdr['id']
        try:
            stream = self.pred.submit(
                arrays['prompt'], max_new_tokens=hdr.get('max_new'),
                beam=hdr.get('beam'),
                deadline_ms=hdr.get('deadline_ms'),
                request_id=hdr.get('request_id'))
        except Exception as e:
            conn.reply_err(req_id, e,
                           _is_requeueable(e, self.draining))
            return
        threading.Thread(target=self._pump,
                         args=(req_id, hdr, stream, conn),
                         daemon=True).start()

    def _pump(self, req_id, hdr, stream, conn):
        try:
            if stream.beam is None and hdr.get('stream'):
                # one frame per DELIVERY BATCH (ISSUE 17): a plain step
                # sends the singleton 'tok' frame, a speculative verify
                # tick coalesces its whole multi-token advance into one
                # 'toks' frame instead of K+1 round-trips
                for batch in stream.batches():
                    if len(batch) == 1:
                        conn.send({'op': 'tok', 'id': req_id,
                                   'tok': int(batch[0])})
                    else:
                        conn.send({'op': 'toks', 'id': req_id,
                                   'toks': [int(t) for t in batch]})
            res = stream.result(600)
        except Exception as e:
            # stream-side failure: the request may have decoded tokens
            # already — only a genuine shed re-routes
            conn.reply_err(req_id, e, _stream_requeueable(e))
            return
        if stream.beam is None:
            conn.send({'op': 'result', 'id': req_id, 'ok': True,
                       'kind': 'greedy'},
                      {'tokens': np.asarray(res, np.int64)})
        else:
            ids, scores = res
            conn.send({'op': 'result', 'id': req_id, 'ok': True,
                       'kind': 'beam'},
                      {'ids': np.asarray(ids, np.int64),
                       'scores': np.asarray(scores, np.float64)})

    def drain(self):
        self.draining = True
        self.pred.drain()

    def snapshot(self):
        return self.pred.stats.snapshot()

    def close(self):
        self.pred.close()


class _CompiledEndpoint(object):
    """Synchronous CompiledPredictor behind a one-thread queue: the
    LoD-capable fallback kind. Requests execute in submit order;
    drain() sheds the queue (re-routable) and waits for the in-flight
    run to deliver."""

    kind = 'compiled'

    def __init__(self, artifact, opts):
        kw = {}
        if opts.get('tier') is not None:
            kw['tier'] = opts['tier']
        if opts.get('platform') is not None:
            kw['platform'] = opts['platform']
        self.pred = _serve.CompiledPredictor(artifact, **kw)
        self.tier = self.pred.tier
        self.draining = False
        self._lock = threading.Lock()
        self._queue = []
        self._wake = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        self._stats = {'requests': 0, 'shed': 0, 'expired': 0}
        self._closed = False
        self._t = threading.Thread(target=self._loop,
                                   name='ptpu-fleet-compiled',
                                   daemon=True)
        self._t.start()
        if opts.get('warmup', True):
            sig = self.pred._sig
            feed = {}
            for e in sig['feeds']:
                data = np.zeros(tuple(e['shape']),
                                np.dtype(e['dtype']))
                lv = int(e.get('lod_levels', 0))
                if lv:
                    offs = [np.zeros(n, np.int32)
                            for n in e['lod_sizes']]
                    feed[e['name']] = (data, offs)
                else:
                    feed[e['name']] = data
            for o in self.pred.run(feed, pad_partial=False):
                np.asarray(o[0] if isinstance(o, tuple) else o)

    def submit(self, hdr, arrays, conn):
        with self._lock:
            if self.draining or self._closed:
                conn.reply_err(hdr['id'],
                               _batching.ServerOverloaded(
                                   'replica draining'), requeue=True)
                return
            # deadline_ms is the REMAINING budget when the frame was
            # written: stamp arrival so endpoint queue time counts too
            self._queue.append((hdr, arrays, conn,
                                time.perf_counter()))
            self._idle.clear()
            self._wake.set()

    def _loop(self):
        while True:
            self._wake.wait()
            with self._lock:
                if not self._queue:
                    self._wake.clear()
                    self._idle.set()
                    if self._closed:
                        return
                    continue
                hdr, arrays, conn, t_in = self._queue.pop(0)
            self._run_one(hdr, arrays, conn, t_in)

    def _run_one(self, hdr, arrays, conn, t_in):
        req_id = hdr['id']
        dl = hdr.get('deadline_ms')
        try:
            if dl is not None and \
                    (time.perf_counter() - t_in) * 1e3 >= dl:
                raise _batching.DeadlineExceeded(
                    'deadline elapsed in the replica queue before '
                    'dispatch%s'
                    % (' (request %s)' % hdr['request_id']
                       if hdr.get('request_id') else ''))
            feed = _serve._feed_from_npz(self.pred._sig['feeds'],
                                         arrays)
            outs = self.pred.run(feed)
        except Exception as e:
            with self._lock:
                key = ('expired' if isinstance(
                    e, _batching.DeadlineExceeded) else None)
                if key:
                    self._stats[key] += 1
            # the run may have dispatched: only sheds re-route
            conn.reply_err(req_id, e, _stream_requeueable(e))
            return
        with self._lock:
            self._stats['requests'] += 1
        lod, flat = [], {}
        for j, o in enumerate(outs):
            if isinstance(o, tuple):
                lod.append(len(o[1]))
                flat['o%d' % j] = o[0]
                for i, off in enumerate(o[1]):
                    flat['o%d.lod%d' % (j, i)] = off
            else:
                lod.append(0)
                flat['o%d' % j] = o
        conn.send({'op': 'result', 'id': req_id, 'ok': True,
                   'n': len(outs), 'lod': lod}, flat)

    def drain(self):
        with self._lock:
            self.draining = True
            shed = list(self._queue)
            self._queue[:] = []
            self._stats['shed'] += len(shed)
        for hdr, _arrays, conn, _t_in in shed:
            conn.reply_err(hdr['id'], _batching.ServerOverloaded(
                'request shed: replica draining for scale-in'),
                requeue=True)
        self._idle.wait(600)

    def snapshot(self):
        with self._lock:
            return {'tier': self.tier,
                    'queue_depth': len(self._queue),
                    'requests': self._stats['requests'],
                    'shed': self._stats['shed'],
                    'expired': self._stats['expired'],
                    'occupancy': 0.0 if self._idle.is_set() else 1.0}

    def close(self):
        with self._lock:
            self._closed = True
            self._wake.set()


_ENDPOINTS = {'batching': _BatchingEndpoint,
              'decoding': _DecodingEndpoint,
              'compiled': _CompiledEndpoint}


def main():
    sock_path, rid, artifact, hb_path, opts_json = sys.argv[1:6]
    rid = int(rid)
    opts = json.loads(opts_json)
    plat = opts.get('platform')
    if plat:
        os.environ['JAX_PLATFORMS'] = plat

    # one process per chip: a replica that cannot get its device (another
    # process holds the chip, or the platform is absent) says so on the
    # router's stderr and exits — the router sees the dead child at once
    # instead of waiting out its spin-up timeout
    import jax
    from jax import monitoring
    from jax._src import xla_bridge
    try:
        jax.devices()
        # with JAX_PLATFORMS unset jax answers a failed accelerator with
        # the cpu and a log line; a replica must not serve there quietly
        failed = dict(xla_bridge._backend_errors)
        if failed:
            raise RuntimeError('; '.join(
                '%s: %s' % kv for kv in sorted(failed.items())))
    except RuntimeError as e:
        sys.stderr.write('fleet worker %d: cannot get its device '
                         '(JAX_PLATFORMS=%r): %s\n'
                         % (rid, os.environ.get('JAX_PLATFORMS'), e))
        sys.exit(3)

    compiles = [0]

    def _listener(event, secs, **kw):
        if event == '/jax/core/compile/backend_compile_duration':
            compiles[0] += 1
    monitoring.register_event_duration_secs_listener(_listener)

    kind = opts.get('kind') or _fleet.detect_kind(artifact)
    endpoint = _ENDPOINTS[kind](artifact, opts)
    state = ['serving']

    hb_stop = threading.Event()

    def _hb_loop():
        interval = float(opts.get('hb_interval_s', 0.5))
        while True:
            try:
                _fleet.write_heartbeat(hb_path, {
                    'replica': rid, 'pid': os.getpid(),
                    'artifact': artifact,
                    'state': state[0], 'kind': kind,
                    'compiles': compiles[0],
                    'stats': endpoint.snapshot()})
            except Exception:
                pass
            if hb_stop.wait(interval):
                return

    hb_t = threading.Thread(target=_hb_loop, name='ptpu-fleet-hb',
                            daemon=True)
    hb_t.start()

    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.connect(sock_path)
    conn = _Conn(sock)
    conn.send({'op': 'hello', 'replica': rid, 'pid': os.getpid(),
               'artifact': artifact,
               'kind': kind, 'tier': endpoint.tier,
               'mesh': getattr(endpoint, 'mesh', None),
               'compiles': compiles[0],
               'framework_free': 'paddle_tpu' not in sys.modules})

    def _drain_then_ack():
        try:
            endpoint.drain()
        finally:
            state[0] = 'drained'
            try:
                conn.send({'op': 'drained', 'replica': rid})
            except OSError:
                pass

    while True:
        try:
            fr = _fleet._recv_frame(sock)
        except Exception:
            fr = None  # EOF or desynced stream: exit; the router's
            #            reader sees the close and fails over
        if fr is None:
            break  # router gone
        hdr, arrays = fr
        op = hdr.get('op')
        if op in ('infer', 'decode'):
            try:
                endpoint.submit(hdr, arrays, conn)
            except Exception as e:
                conn.reply_err(hdr.get('id'), e)
        elif op == 'drain':
            state[0] = 'draining'
            threading.Thread(target=_drain_then_ack,
                             daemon=True).start()
        elif op == 'stop':
            break
    state[0] = 'stopped'
    try:
        endpoint.close()
    except Exception:
        pass
    hb_stop.set()
    hb_t.join(timeout=5)
    try:
        _fleet.write_heartbeat(hb_path, {
            'replica': rid, 'pid': os.getpid(), 'state': 'stopped',
            'compiles': compiles[0]})
    except Exception:
        pass
    try:
        conn.send({'op': 'bye', 'replica': rid})
    except OSError:
        pass
    sock.close()


if __name__ == '__main__':
    main()
