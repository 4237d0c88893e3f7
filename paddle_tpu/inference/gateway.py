"""HTTP serving gateway (ISSUE 19 tentpole): the network front door
over the replica fleet.

`Gateway` fronts a `fleet.FleetRouter` — or any single predictor that
honors the existing submit/`drain()` contract (`DecodingPredictor`,
`BatchingPredictor`) — with a stdlib-only threaded HTTP/1.1 server:

1. **JSON + base64-npz codec** — request bodies are JSON; arrays ride
   as a base64-encoded npz blob (numpy's own validated binary format,
   `allow_pickle=False`, the fleet wire discipline) under the `npz`
   key, with the `<name>` / `<name>.lodN` convention for LoD feeds.
   Decode prompts may be a plain JSON int list instead.
2. **SSE token streaming** — `POST /v1/decode` with `stream` (greedy
   only) answers `text/event-stream`: one `data: {"toks": [...]}`
   event per DELIVERY BATCH (a speculative verify tick's coalesced
   multi-token advance — ISSUE 17 — stays one event), then an
   `event: done` carrying the full transcript. The stream rides the
   existing `TokenStream.batches()` / fleet `on_token` frames.
3. **Multi-tenant admission control** — per-tenant API keys
   (`X-API-Key` / `Authorization: Bearer`), token-bucket rate limiting
   (429 + Retry-After), and per-tenant `max_inflight` quotas, all
   applied at the door BEFORE the backend sees the request. Backend
   shedding maps onto HTTP statuses, never a silent drop:
   `ServerOverloaded`/`FleetUnavailable` -> 503 + Retry-After,
   `DeadlineExceeded` -> 504, `ReplicaFailed` -> 502, validation ->
   400. A failure after the SSE headers are out arrives as an
   `event: error` frame with the same code.
4. **Deadline propagation** — a request's `deadline_ms` budget starts
   at HTTP accept: the gateway sheds at its own door when the budget
   is already gone, and passes the REMAINING budget to the backend so
   router-queue and mid-decode expiry (the existing semantics) share
   one clock.
5. **Request ids** — every request gets a `request_id` (client
   `X-Request-Id` wins), echoed in the response header, threaded
   through the fleet frame headers into replica stats, and surfaced in
   every error message end to end.
6. **Observability** — `/healthz`, `/stats.json` (gateway + backend
   snapshot), `/metrics` (Prometheus text exposition: per-tenant
   request/shed/rate-limit counters, TTFB/TTFT percentiles, plus the
   existing fleet/serving/decode counters flattened). The snapshot
   also registers with `profiler.register_gateway_source` so
   `gateway_report()` renders next to the serving tables.
7. **Graceful drain** — `drain()` stops admitting (new data requests
   answer 503 'gateway draining'), finishes every in-flight request /
   stream, then stops the listener; the `serve.py gateway` CLI wires
   SIGTERM to exactly this and exits 0.

Framework-free: stdlib + numpy + the sibling serving modules only; a
gateway process never imports jax — the replicas do the serving.
"""
import base64
import io
import itertools
import json
import os
import queue as _queue_mod
import sys
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

try:
    from . import serve as _serve
    from . import batching as _batching
    from . import decoding as _decoding
    from . import fleet as _fleet
except ImportError:  # imported by file path: siblings sit alongside
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serve as _serve
    import batching as _batching
    import decoding as _decoding
    import fleet as _fleet

_maybe_profiler = _serve._maybe_profiler
_SOURCE_SEQ = _serve._SOURCE_SEQ
_percentiles = _decoding._percentiles
ServerOverloaded = _batching.ServerOverloaded
DeadlineExceeded = _batching.DeadlineExceeded
ReplicaFailed = _fleet.ReplicaFailed
FleetUnavailable = _fleet.FleetUnavailable

_MAX_BODY = 1 << 28          # request-body sanity bound (protocol, not data)
_STREAM_RESULT_TIMEOUT = 600.0


def status_for(exc):
    """The HTTP status one backend error maps to — the gateway's whole
    error-code contract in one place (never a silent drop)."""
    if isinstance(exc, DeadlineExceeded):
        return 504
    if isinstance(exc, (ReplicaFailed,)):
        return 502
    if isinstance(exc, (ServerOverloaded, FleetUnavailable)):
        return 503
    if isinstance(exc, (ValueError, TypeError, KeyError)):
        return 400
    if isinstance(exc, TimeoutError):
        return 504
    return 500


_CATEGORY = {429: 'rate_limited', 503: 'shed', 504: 'expired',
             502: 'failed', 500: 'failed'}


def _category(code):
    """Counter bucket for a response code: ok / bad (4xx client) /
    rate_limited / shed / expired / failed."""
    if code < 300:
        return 'ok'
    return _CATEGORY.get(code, 'bad')


def encode_arrays(arrays):
    """{name: array} -> base64 npz string (the response/request codec)."""
    buf = io.BytesIO()
    np.savez(buf, **{k: np.asarray(v) for k, v in arrays.items()})
    return base64.b64encode(buf.getvalue()).decode('ascii')


def decode_arrays(b64):
    """base64 npz string -> {name: array}; pickle stays off (the fleet
    wire discipline — a gateway must never unpickle client bytes)."""
    raw = base64.b64decode(b64, validate=True)
    with np.load(io.BytesIO(raw), allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def _feeds_from_arrays(arrays):
    """Flat '<name>' + '<name>.lodN' arrays -> the submit() convention:
    {name: array} with LoD feeds as (data, [offsets...]) pairs."""
    feeds, lods = {}, {}
    for k, v in arrays.items():
        if '.lod' in k:
            name, idx = k.rsplit('.lod', 1)
            lods.setdefault(name, {})[int(idx)] = np.asarray(v, np.int32)
        else:
            feeds[k] = v
    for name, offs in lods.items():
        if name not in feeds:
            raise ValueError('lod offsets for unknown feed %r' % name)
        feeds[name] = (feeds[name],
                       [offs[i] for i in sorted(offs)])
    return feeds


class TenantConfig(object):
    """One tenant's admission policy. `rate` is a req/s token-bucket
    refill (None = unlimited) with `burst` capacity; `max_inflight`
    bounds the tenant's concurrently-admitted requests (None =
    unlimited); `admin` grants the control endpoints (/admin/*)."""

    def __init__(self, name, rate=None, burst=None, max_inflight=None,
                 admin=False):
        self.name = name
        self.rate = float(rate) if rate else None
        self.burst = float(burst if burst is not None
                           else max(1.0, self.rate or 1.0))
        self.max_inflight = (int(max_inflight)
                             if max_inflight is not None else None)
        self.admin = bool(admin)
        # token bucket (guarded by the gateway stats lock)
        self.tokens = self.burst
        self.t_refill = time.perf_counter()

    def acquire(self):
        """(admitted, retry_after_s). Caller holds the gateway lock."""
        if self.rate is None:
            return True, 0.0
        now = time.perf_counter()
        self.tokens = min(self.burst,
                          self.tokens + (now - self.t_refill) * self.rate)
        self.t_refill = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True, 0.0
        return False, (1.0 - self.tokens) / self.rate


def tenants_from_json(path_or_dict):
    """{api_key: {tenant, rate, burst, max_inflight, admin}} (a path to
    a JSON file, or the dict itself) -> {api_key: TenantConfig}."""
    cfg = path_or_dict
    if isinstance(cfg, str):
        with open(cfg) as f:
            cfg = json.load(f)
    out = {}
    for key, spec in cfg.items():
        spec = dict(spec or {})
        out[key] = TenantConfig(
            spec.get('tenant') or spec.get('name') or key,
            rate=spec.get('rate'), burst=spec.get('burst'),
            max_inflight=spec.get('max_inflight'),
            admin=spec.get('admin', False))
    return out


class GatewayStats(object):
    """Thread-safe gateway counters: per-tenant request outcomes by
    code, inflight gauges, and TTFB/TTFT sliding windows. `snapshot()`
    is the profiler gateway-source contract (kind='gateway')."""

    _CATS = ('ok', 'bad', 'rate_limited', 'quota', 'shed', 'expired',
             'failed')

    def __init__(self, window=8192):
        self._lock = threading.Lock()
        self._ttfb = deque(maxlen=window)
        self._ttft = deque(maxlen=window)
        self.tenants = {}        # name -> {requests, codes, <cats>...}
        self.inflight = 0
        self.streams = 0         # SSE streams served to completion
        self.disconnects = 0     # client gone mid-response
        self.draining = False

    def _tenant(self, name):
        t = self.tenants.get(name)
        if t is None:
            t = {'requests': 0, 'inflight': 0,
                 'codes': {}}
            t.update({c: 0 for c in self._CATS})
            self.tenants[name] = t
        return t

    def record(self, tenant, code, category=None, ttfb_s=None,
               ttft_s=None):
        """One resolved request: every admitted-or-rejected request
        lands here exactly once — the zero-silent-drops ledger."""
        with self._lock:
            t = self._tenant(tenant)
            t['requests'] += 1
            t['codes'][str(code)] = t['codes'].get(str(code), 0) + 1
            cat = category or _category(code)
            t[cat] = t.get(cat, 0) + 1
            if ttfb_s is not None:
                self._ttfb.append(ttfb_s)
            if ttft_s is not None:
                self._ttft.append(ttft_s)

    def snapshot(self):
        with self._lock:
            b50, b99 = _percentiles(list(self._ttfb), [50, 99])
            t50, t99 = _percentiles(list(self._ttft), [50, 99])
            tenants = {name: dict(t, codes=dict(t['codes']))
                       for name, t in self.tenants.items()}
            totals = {c: sum(t[c] for t in tenants.values())
                      for c in self._CATS}
            return dict(totals,
                        kind='gateway',
                        requests=sum(t['requests']
                                     for t in tenants.values()),
                        inflight=int(self.inflight),
                        streams=int(self.streams),
                        disconnects=int(self.disconnects),
                        draining=bool(self.draining),
                        ttfb_p50_ms=b50, ttfb_p99_ms=b99,
                        ttft_p50_ms=t50, ttft_p99_ms=t99,
                        tenants=tenants)


# -- Prometheus text exposition ----------------------------------------------

def _prom_escape(v):
    return str(v).replace('\\', r'\\').replace('"', r'\"').replace(
        '\n', r'\n')


def _prom_line(lines, name, value, labels=None):
    lab = ''
    if labels:
        lab = '{%s}' % ','.join('%s="%s"' % (k, _prom_escape(v))
                                for k, v in sorted(labels.items()))
    lines.append('%s%s %s' % (name, lab, repr(float(value))))


def _prom_scalars(lines, prefix, snap, labels=None, _seen=None):
    """Flatten one snapshot dict's numeric scalars into metric lines
    (nested dicts/lists are rendered by the callers that know their
    shape; bools count as 0/1)."""
    for key in sorted(snap):
        v = snap[key]
        if isinstance(v, bool):
            v = int(v)
        if isinstance(v, (int, float)) and not isinstance(v, complex):
            _prom_line(lines, '%s_%s' % (prefix, key), v, labels)


def render_metrics(gateway_snap, backend_snap=None):
    """Prometheus text exposition (version 0.0.4) for the gateway
    counters plus the backend (fleet/serving/decode) snapshot."""
    lines = []
    g = gateway_snap
    lines.append('# HELP ptpu_gateway_requests_total Requests resolved '
                 'per tenant and HTTP status code.')
    lines.append('# TYPE ptpu_gateway_requests_total counter')
    for tenant, t in sorted(g.get('tenants', {}).items()):
        for code, n in sorted(t['codes'].items()):
            _prom_line(lines, 'ptpu_gateway_requests_total', n,
                       {'tenant': tenant, 'code': code})
    for cat, help_ in (('rate_limited', 'Requests answered 429 by the '
                        'token bucket.'),
                       ('quota', 'Requests rejected on the per-tenant '
                        'max_inflight quota.'),
                       ('shed', 'Requests shed with 503.'),
                       ('expired', 'Requests expired with 504.'),
                       ('failed', 'Requests failed with 502/500.')):
        lines.append('# HELP ptpu_gateway_%s_total %s' % (cat, help_))
        lines.append('# TYPE ptpu_gateway_%s_total counter' % cat)
        for tenant, t in sorted(g.get('tenants', {}).items()):
            _prom_line(lines, 'ptpu_gateway_%s_total' % cat,
                       t.get(cat, 0), {'tenant': tenant})
    lines.append('# HELP ptpu_gateway_inflight Requests currently '
                 'admitted and unresolved.')
    lines.append('# TYPE ptpu_gateway_inflight gauge')
    _prom_line(lines, 'ptpu_gateway_inflight', g.get('inflight', 0))
    lines.append('# TYPE ptpu_gateway_draining gauge')
    _prom_line(lines, 'ptpu_gateway_draining',
               1 if g.get('draining') else 0)
    for met, desc in (('ttfb_ms', 'Time to first response byte'),
                      ('ttft_ms', 'Time to first streamed token')):
        lines.append('# HELP ptpu_gateway_%s %s (sliding window).'
                     % (met, desc))
        lines.append('# TYPE ptpu_gateway_%s summary' % met)
        for q, key in (('0.5', '%s_p50_ms'), ('0.99', '%s_p99_ms')):
            _prom_line(lines, 'ptpu_gateway_%s' % met,
                       g.get(key % met[:4], 0.0), {'quantile': q})
    if backend_snap:
        kind = backend_snap.get('kind', 'backend')
        prefix = 'ptpu_%s' % kind
        lines.append('# HELP %s_info Backend serving counters '
                     '(profiler snapshot contract).' % prefix)
        lines.append('# TYPE %s_info gauge' % prefix)
        _prom_scalars(lines, prefix, backend_snap)
        for rid, rep in sorted(backend_snap.get('replicas',
                                                {}).items()):
            _prom_scalars(lines, '%s_replica' % prefix,
                          {k: v for k, v in rep.items()
                           if k != 'stats'},
                          {'replica': str(rid)})
    return '\n'.join(lines) + '\n'


# -- backend adapters --------------------------------------------------------

class _Backend(object):
    """Uniform view over FleetRouter / DecodingPredictor /
    BatchingPredictor: kind, snapshot, healthy, and the two dispatch
    shapes (request/response + streamed decode)."""

    def __init__(self, target):
        self.target = target
        if isinstance(target, _fleet.FleetRouter):
            self.flavor = 'fleet'
            self.kind = target.kind
        elif isinstance(target, _decoding.DecodingPredictor):
            self.flavor, self.kind = 'direct', 'decoding'
        elif isinstance(target, _batching.BatchingPredictor):
            self.flavor, self.kind = 'direct', 'batching'
        else:
            raise TypeError(
                'gateway backend must be a FleetRouter, '
                'DecodingPredictor or BatchingPredictor, got %r'
                % type(target).__name__)

    def snapshot(self):
        if self.flavor == 'fleet':
            return self.target.fleet_snapshot()
        return self.target.stats.snapshot()

    def healthy(self):
        if self.flavor == 'fleet':
            try:
                return self.target.status()['serving'] >= 1
            except Exception:
                return False
        return not getattr(self.target, '_closed', False)

    def infer(self, feeds, deadline_ms, request_id):
        """Dense request/response; returns (outputs list, lod levels)."""
        if self.kind == 'decoding':
            raise ValueError(
                'this gateway serves a decode artifact — POST '
                '/v1/decode (request %s)' % request_id)
        fut = self.target.submit(feeds, deadline_ms=deadline_ms,
                                 request_id=request_id)
        outs = fut.result(_STREAM_RESULT_TIMEOUT)
        lod = [len(o[1]) if isinstance(o, tuple) else 0 for o in outs]
        return outs, lod

    def decode(self, prompt, max_new, beam, deadline_ms, request_id):
        """Non-streamed decode; returns the transcript result."""
        if self.kind != 'decoding':
            raise ValueError('this gateway serves a dense artifact — '
                             'POST /v1/infer (request %s)' % request_id)
        if self.flavor == 'fleet':
            fut = self.target.submit(prompt, deadline_ms=deadline_ms,
                                     max_new_tokens=max_new, beam=beam,
                                     request_id=request_id)
            return fut.result(_STREAM_RESULT_TIMEOUT)
        stream = self.target.submit(prompt, max_new_tokens=max_new,
                                    beam=beam, deadline_ms=deadline_ms,
                                    request_id=request_id)
        return stream.result(_STREAM_RESULT_TIMEOUT)

    def decode_stream(self, prompt, max_new, deadline_ms, request_id):
        """Streamed greedy decode: yields ('toks', [ints]) delivery
        batches then ('done', transcript); backend errors raise."""
        if self.kind != 'decoding':
            raise ValueError('this gateway serves a dense artifact — '
                             'POST /v1/infer (request %s)' % request_id)
        if self.flavor == 'direct':
            stream = self.target.submit(
                prompt, max_new_tokens=max_new, deadline_ms=deadline_ms,
                request_id=request_id)
            for batch in stream.batches():
                yield 'toks', batch
            yield 'done', stream.result(_STREAM_RESULT_TIMEOUT)
            return
        # fleet: ride on_token from the router reader thread. Tokens of
        # one coalesced 'toks' frame fire back-to-back with no network
        # round-trip between them, so the greedy drain below re-batches
        # them into one SSE event.
        q = _queue_mod.Queue()
        fut = self.target.submit(prompt, deadline_ms=deadline_ms,
                                 max_new_tokens=max_new,
                                 on_token=q.put, request_id=request_id)
        fut.add_done_callback(lambda f: q.put(_DONE))
        while True:
            item = q.get(timeout=_STREAM_RESULT_TIMEOUT)
            if item is _DONE:
                break
            batch = [item]
            while True:
                try:
                    nxt = q.get_nowait()
                except _queue_mod.Empty:
                    break
                if nxt is _DONE:
                    yield 'toks', batch
                    yield 'done', fut.result(0)
                    return
                batch.append(nxt)
            yield 'toks', batch
        yield 'done', fut.result(0)


_DONE = object()


# -- the HTTP server ---------------------------------------------------------

class _Handler(BaseHTTPRequestHandler):
    protocol_version = 'HTTP/1.1'
    server_version = 'ptpu-gateway'
    gateway = None  # set by the per-Gateway handler subclass

    # quiet by default: one line per request through the gateway's own
    # counters, not BaseHTTPRequestHandler's stderr chatter
    def log_message(self, fmt, *args):
        if os.environ.get('PTPU_GATEWAY_LOG'):
            BaseHTTPRequestHandler.log_message(self, fmt, *args)

    def do_GET(self):
        gw = self.gateway
        path = self.path.split('?', 1)[0]
        if path == '/healthz':
            return gw._h_healthz(self)
        if path == '/stats.json':
            return gw._h_stats(self)
        if path == '/metrics':
            return gw._h_metrics(self)
        gw._reply_json(self, 404, {'error': 'no route %s' % path,
                                   'etype': 'NotFound'},
                       tenant='-')

    def do_POST(self):
        gw = self.gateway
        path = self.path.split('?', 1)[0]
        if path == '/v1/infer':
            return gw._h_infer(self)
        if path == '/v1/decode':
            return gw._h_decode(self)
        if path == '/admin/drain':
            return gw._h_drain(self)
        gw._reply_json(self, 404, {'error': 'no route %s' % path,
                                   'etype': 'NotFound'},
                       tenant='-')


class Gateway(object):
    """The HTTP front door. `backend` is a FleetRouter or a single
    predictor; the gateway NEVER owns it (close() stops the HTTP tier
    only — the caller closes the backend, mirroring who opened it).

    Endpoints:
        GET  /healthz     liveness + serving capacity (200 / 503)
        GET  /stats.json  gateway + backend snapshot
        GET  /metrics     Prometheus text exposition
        POST /v1/infer    dense request/response (base64-npz feeds)
        POST /v1/decode   decode; `stream` answers SSE token events
        POST /admin/drain graceful drain (admin tenants only)

    `tenants` is {api_key: TenantConfig} (see tenants_from_json); None
    serves anonymously with no limits. `max_inflight` bounds the
    gateway-wide admitted requests (503 beyond it)."""

    def __init__(self, backend, host='127.0.0.1', port=0, tenants=None,
                 max_inflight=None, default_deadline_ms=None,
                 stats_window=8192):
        self.backend = _Backend(backend)
        self._tenants = dict(tenants) if tenants else None
        self._anon = TenantConfig('anonymous', admin=True)
        self._max_inflight = (int(max_inflight)
                              if max_inflight is not None else None)
        self._default_deadline_ms = default_deadline_ms
        self.stats = GatewayStats(stats_window)
        # one lock for admission + counters: the tenant table is
        # touched by both the door checks and the outcome ledger
        self._lock = self.stats._lock
        self._idle = threading.Condition(self._lock)
        self._draining = False
        self._req_seq = itertools.count()
        self.drain_requested = threading.Event()

        handler = type('_BoundHandler', (_Handler,), {'gateway': self})
        self._server = ThreadingHTTPServer((host, port), handler)
        self._server.daemon_threads = True
        self._serve_t = None
        self._served = threading.Event()  # a serve loop has run

        self._profiler_name = None
        prof = _maybe_profiler()
        if prof is not None and hasattr(prof, 'register_gateway_source'):
            name = 'gateway:%s:%d#%d' % (self.address[0],
                                         self.address[1],
                                         next(_SOURCE_SEQ))
            prof.register_gateway_source(name, self.snapshot)
            self._profiler_name = name

    # -- lifecycle ---------------------------------------------------------
    @property
    def address(self):
        return self._server.server_address[:2]

    @property
    def url(self):
        return 'http://%s:%d' % self.address

    def start(self):
        """Serve in a background thread; returns self."""
        if self._serve_t is None:
            self._served.set()
            self._serve_t = threading.Thread(
                target=self._server.serve_forever,
                kwargs={'poll_interval': 0.1},
                name='ptpu-gateway', daemon=True)
            self._serve_t.start()
        return self

    def serve_forever(self):
        self._served.set()
        self._server.serve_forever(poll_interval=0.1)

    def _shutdown_server(self):
        # BaseServer.shutdown() blocks until serve_forever() exits; if
        # no serve loop ever ran it would wait forever, so only signal
        # a loop that actually started.
        if self._served.is_set():
            self._server.shutdown()

    def drain(self, timeout=None):
        """Graceful drain: stop admitting (new data requests answer 503
        'gateway draining'), finish every in-flight request/stream, then
        stop accepting connections. Returns True when the gateway went
        idle within `timeout` — in-flight work is never cut off early
        either way (the zero-dropped-streams contract)."""
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        with self._lock:
            self._draining = True
            self.stats.draining = True
            while self.stats.inflight > 0:
                wait = None
                if deadline is not None:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        break
                self._idle.wait(wait if wait is not None else 1.0)
            idle = self.stats.inflight == 0
        self._shutdown_server()
        return idle

    def close(self):
        """Stop the HTTP tier (the backend stays up — its owner closes
        it). Idempotent."""
        with self._lock:
            self._draining = True
            self.stats.draining = True
        self._shutdown_server()
        self._server.server_close()
        if self._serve_t is not None:
            self._serve_t.join(timeout=5)
            self._serve_t = None
        name, self._profiler_name = self._profiler_name, None
        if name:
            prof = _maybe_profiler()
            if prof is not None and hasattr(prof,
                                            'unregister_gateway_source'):
                prof.unregister_gateway_source(name)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()
        return False

    # -- snapshots ---------------------------------------------------------
    def snapshot(self):
        """Profiler gateway-source contract: gateway counters + the
        backend snapshot under 'backend'."""
        snap = self.stats.snapshot()
        snap['addr'] = '%s:%d' % self.address
        try:
            snap['backend'] = self.backend.snapshot()
        except Exception:
            snap['backend'] = {}
        return snap

    # -- admission ---------------------------------------------------------
    def _auth(self, handler):
        """-> TenantConfig, or None after answering 401."""
        if self._tenants is None:
            return self._anon
        key = handler.headers.get('X-API-Key')
        if not key:
            auth = handler.headers.get('Authorization', '')
            if auth.startswith('Bearer '):
                key = auth[len('Bearer '):].strip()
        tenant = self._tenants.get(key) if key else None
        if tenant is None:
            self._reply_json(handler, 401,
                            {'error': 'missing or unknown API key',
                             'etype': 'Unauthorized'}, tenant='-')
            return None
        return tenant

    def _admit(self, handler, tenant, rid):
        """Door checks under one lock: drain, rate, quotas. Returns
        True when admitted (inflight charged); False after replying."""
        with self._lock:
            if self._draining:
                code, cat, hdrs, msg = 503, 'shed', \
                    {'Retry-After': '1'}, 'gateway draining'
            else:
                ok, retry_s = tenant.acquire()
                if not ok:
                    code, cat = 429, 'rate_limited'
                    hdrs = {'Retry-After': '%d' % max(1, int(retry_s
                                                             + 0.999))}
                    msg = ('tenant %s over its %.3g req/s rate — '
                           'request rate-limited'
                           % (tenant.name, tenant.rate))
                elif tenant.max_inflight is not None and \
                        self.stats._tenant(tenant.name)['inflight'] \
                        >= tenant.max_inflight:
                    code, cat, hdrs = 429, 'quota', {'Retry-After': '1'}
                    msg = ('tenant %s at max_inflight %d — request '
                           'shed at the gateway door'
                           % (tenant.name, tenant.max_inflight))
                elif self._max_inflight is not None and \
                        self.stats.inflight >= self._max_inflight:
                    code, cat, hdrs = 503, 'shed', {'Retry-After': '1'}
                    msg = ('gateway at max_inflight %d — request shed '
                           'at the gateway door' % self._max_inflight)
                else:
                    self.stats.inflight += 1
                    self.stats._tenant(tenant.name)['inflight'] += 1
                    return True
        self._reply_json(handler, code,
                        {'error': '%s (request %s)' % (msg, rid),
                         'etype': 'ServerOverloaded'
                         if code == 503 else 'RateLimited',
                         'request_id': rid},
                        tenant=tenant.name, category=cat,
                        headers=hdrs)
        return False

    def _release(self, tenant):
        with self._lock:
            self.stats.inflight -= 1
            self.stats._tenant(tenant.name)['inflight'] -= 1
            if self.stats.inflight == 0:
                self._idle.notify_all()

    # -- response helpers --------------------------------------------------
    def _reply_json(self, handler, code, obj, tenant, category=None,
                    headers=None, t0=None):
        body = json.dumps(obj).encode('utf-8')
        ttfb = (time.perf_counter() - t0) if t0 is not None else None
        # counted BEFORE the reply goes out: a client that has its reply
        # must find its own request in the stats it reads next
        if tenant != '-':
            self.stats.record(tenant, code, category, ttfb_s=ttfb)
        try:
            handler.send_response(code)
            handler.send_header('Content-Type', 'application/json')
            handler.send_header('Content-Length', str(len(body)))
            if obj.get('request_id'):
                handler.send_header('X-Request-Id', obj['request_id'])
            for k, v in (headers or {}).items():
                handler.send_header(k, v)
            handler.end_headers()
            handler.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            with self._lock:
                self.stats.disconnects += 1

    def _reply_error(self, handler, exc, rid, tenant, t0=None):
        code = status_for(exc)
        headers = {'Retry-After': '1'} if code in (429, 503) else None
        self._reply_json(handler, code,
                        {'error': str(exc),
                         'etype': type(exc).__name__,
                         'request_id': rid, 'code': code},
                        tenant=tenant, headers=headers, t0=t0)

    @staticmethod
    def _read_body(handler):
        n = int(handler.headers.get('Content-Length') or 0)
        if n > _MAX_BODY:
            raise ValueError('request body of %d bytes exceeds the %d '
                             'gateway bound' % (n, _MAX_BODY))
        raw = handler.rfile.read(n) if n else b'{}'
        return json.loads(raw.decode('utf-8'))

    def _request_id(self, handler):
        return (handler.headers.get('X-Request-Id')
                or 'gw-%d-%d' % (os.getpid(), next(self._req_seq)))

    def _remaining_ms(self, body, t0):
        """The budget left when the backend is about to see the request
        (time at the gateway counts); None = no deadline. Raises
        DeadlineExceeded when already spent — the gateway-door shed."""
        budget = body.get('deadline_ms', self._default_deadline_ms)
        if budget is None:
            return None
        remaining = float(budget) - (time.perf_counter() - t0) * 1e3
        if remaining <= 0:
            raise DeadlineExceeded(
                'deadline elapsed at the gateway door (budget %.1f ms)'
                % float(budget))
        return remaining

    # -- route handlers ----------------------------------------------------
    def _h_healthz(self, handler):
        healthy = self.backend.healthy() and not self._draining
        code = 200 if healthy else 503
        self._reply_json(handler, code,
                        {'ok': healthy, 'draining': self._draining,
                         'kind': self.backend.kind,
                         'inflight': self.stats.inflight},
                        tenant='-')

    def _h_stats(self, handler):
        self._reply_json(handler, 200, self.snapshot(), tenant='-')

    def _h_metrics(self, handler):
        snap = self.snapshot()
        text = render_metrics(snap, snap.get('backend'))
        body = text.encode('utf-8')
        try:
            handler.send_response(200)
            handler.send_header('Content-Type',
                                'text/plain; version=0.0.4')
            handler.send_header('Content-Length', str(len(body)))
            handler.end_headers()
            handler.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            with self._lock:
                self.stats.disconnects += 1

    def _h_drain(self, handler):
        tenant = self._auth(handler)
        if tenant is None:
            return
        if not tenant.admin:
            self._reply_json(handler, 403,
                            {'error': 'tenant %s is not an admin'
                             % tenant.name, 'etype': 'Forbidden'},
                            tenant=tenant.name, category='bad')
            return
        self.drain_requested.set()
        with self._lock:
            self._draining = True
            self.stats.draining = True
        self._reply_json(handler, 202,
                        {'draining': True,
                         'inflight': self.stats.inflight},
                        tenant=tenant.name)

    def _h_infer(self, handler):
        t0 = time.perf_counter()
        rid = self._request_id(handler)
        tenant = self._auth(handler)
        if tenant is None:
            return
        if not self._admit(handler, tenant, rid):
            return
        try:
            try:
                body = self._read_body(handler)
                if 'npz' not in body:
                    raise ValueError(
                        "infer body needs 'npz' (base64 npz of the "
                        "feed arrays)")
                feeds = _feeds_from_arrays(decode_arrays(body['npz']))
                remaining = self._remaining_ms(body, t0)
                outs, lod = self.backend.infer(feeds, remaining, rid)
            except Exception as e:
                self._reply_error(handler, e, rid, tenant.name, t0=t0)
                return
            flat = {}
            for j, o in enumerate(outs):
                if isinstance(o, tuple):
                    flat['o%d' % j] = o[0]
                    for i, off in enumerate(o[1]):
                        flat['o%d.lod%d' % (j, i)] = off
                else:
                    flat['o%d' % j] = o
            self._reply_json(handler, 200,
                            {'npz': encode_arrays(flat), 'lod': lod,
                             'n': len(outs), 'request_id': rid},
                            tenant=tenant.name, t0=t0)
        finally:
            self._release(tenant)

    def _h_decode(self, handler):
        t0 = time.perf_counter()
        rid = self._request_id(handler)
        tenant = self._auth(handler)
        if tenant is None:
            return
        if not self._admit(handler, tenant, rid):
            return
        try:
            try:
                body = self._read_body(handler)
                prompt = body.get('prompt')
                if prompt is None and 'npz' in body:
                    prompt = decode_arrays(body['npz']).get('prompt')
                if prompt is None:
                    raise ValueError(
                        "decode body needs 'prompt' (JSON int list) or "
                        "'npz' with a 'prompt' array")
                prompt = np.asarray(prompt, np.int64).reshape(-1)
                max_new = body.get('max_new_tokens')
                beam = body.get('beam')
                stream = bool(body.get('stream', beam is None))
                remaining = self._remaining_ms(body, t0)
            except Exception as e:
                self._reply_error(handler, e, rid, tenant.name, t0=t0)
                return
            if stream and beam is None:
                self._decode_sse(handler, tenant, rid, prompt, max_new,
                                 remaining, t0)
                return
            try:
                res = self.backend.decode(prompt, max_new, beam,
                                          remaining, rid)
            except Exception as e:
                self._reply_error(handler, e, rid, tenant.name, t0=t0)
                return
            if beam is None:
                out = {'tokens': [int(t) for t in res],
                       'request_id': rid}
            else:
                ids, scores = res
                out = {'ids': np.asarray(ids).tolist(),
                       'scores': np.asarray(scores).tolist(),
                       'request_id': rid}
            self._reply_json(handler, 200, out, tenant=tenant.name,
                            t0=t0)
        finally:
            self._release(tenant)

    def _decode_sse(self, handler, tenant, rid, prompt, max_new,
                    deadline_ms, t0):
        """Greedy streamed decode as Server-Sent Events. The error
        contract survives the streaming split: before the first byte a
        failure is a plain HTTP status; after it, an `event: error`
        frame carrying the same code — never a silent cut."""
        events = self.backend.decode_stream(prompt, max_new,
                                            deadline_ms, rid)
        headers_out = False
        ttfb = None
        ttft = None
        n_sent = 0
        done = False
        try:
            for kind, payload in events:
                if not headers_out:
                    handler.send_response(200)
                    handler.send_header('Content-Type',
                                        'text/event-stream')
                    handler.send_header('Cache-Control', 'no-cache')
                    handler.send_header('X-Request-Id', rid)
                    handler.send_header('Connection', 'close')
                    handler.end_headers()
                    handler.close_connection = True
                    headers_out = True
                    ttfb = time.perf_counter() - t0
                if kind == 'toks':
                    if ttft is None:
                        ttft = time.perf_counter() - t0
                    n_sent += len(payload)
                    self._sse(handler, None,
                              {'toks': [int(t) for t in payload]})
                else:  # done: counted BEFORE its last frame goes out
                    # (the _reply_json order), so a client that has its
                    # whole reply finds its request in the stats
                    with self._lock:
                        self.stats.streams += 1
                    self.stats.record(tenant.name, 200, ttfb_s=ttfb,
                                      ttft_s=ttft)
                    done = True
                    self._sse(handler, 'done',
                              {'tokens': [int(t) for t in payload],
                               'n': len(payload), 'request_id': rid})
        except (BrokenPipeError, ConnectionResetError):
            with self._lock:
                self.stats.disconnects += 1
            if not done:
                self.stats.record(tenant.name, 499, category='failed')
        except Exception as e:
            if done:        # the last frame's write failed: counted already
                return
            code = status_for(e)
            if not headers_out:
                self._reply_error(handler, e, rid, tenant.name, t0=t0)
                return
            try:
                self._sse(handler, 'error',
                          {'error': str(e), 'etype': type(e).__name__,
                           'code': code, 'request_id': rid,
                           'n_sent': n_sent})
            except (BrokenPipeError, ConnectionResetError):
                with self._lock:
                    self.stats.disconnects += 1
            self.stats.record(tenant.name, code, ttft_s=ttft)

    @staticmethod
    def _sse(handler, event, obj):
        frame = b''
        if event:
            frame += b'event: %s\n' % event.encode('ascii')
        frame += b'data: %s\n\n' % json.dumps(obj).encode('utf-8')
        handler.wfile.write(frame)
        handler.wfile.flush()
