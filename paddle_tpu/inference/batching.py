"""Dynamic-batching serving over compiled artifacts (ISSUE 1 tentpole).

The reference's deployment API serves one request per `Run` call
(inference/api/paddle_api.h:1), so small-batch serving pays the full
per-dispatch cost on every request. `BatchingPredictor` amortizes it the
way modern serving systems do (Clipper-style adaptive batching; the
request-level simplification of ORCA's iteration scheduling, which is
what fixed-shape artifacts admit):

1. **Request queue + coalescing loop** — callers `submit()` requests
   (any row count); a worker thread coalesces them into one batch under
   a `max_batch_size` / `batch_timeout_ms` policy and dispatches ONE
   compiled call for the whole batch, slicing per-request results back
   to each caller's `Future`.
2. **Multi-bucket artifacts** — one artifact dir carries several batch
   sizes (export_compiled(..., batch_sizes=[1, 8, 32, 128])); the
   coalescer pads up to the SMALLEST bucket that fits, the batched
   analog of the LoD `bucket_rows` discipline (serve.py _build_args).
3. **Async double-buffered dispatch** — the coalescing thread hands
   dispatched (still in-flight) device results to a delivery thread
   through a depth-limited queue and immediately starts coalescing and
   padding the NEXT batch; JAX async dispatch overlaps batch N's device
   execution with batch N+1's host work, and `np.asarray` (block until
   ready) happens only at delivery.
4. **Serving metrics** — queue depth, batch occupancy (filled rows /
   bucket rows), and p50/p95/p99 request latency, readable via
   `stats.snapshot()` and surfaced through `paddle_tpu.profiler`'s
   serving report when the framework is loaded.

Determinism contract: per-request outputs are bit-identical to an
unbatched `CompiledPredictor.run` through the SAME bucket (row position
inside a compiled batch does not change per-row results); different
buckets compile different shapes and may differ in the last bit, as with
any XLA batch-size change.

Framework-free: imports only stdlib + numpy (+ sibling serve.py, which
imports jax lazily). `paddle_tpu.profiler` is touched ONLY when the
framework is already loaded in the process, so a serving process stays
tracer-free (serve.py docstring contract).
"""
import json
import os
import queue
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

try:
    from . import serve as _serve
except ImportError:  # imported by file path: serve.py sits alongside
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import serve as _serve

_STOP = object()
# canonical copies live in serve.py (already imported either way)
_SOURCE_SEQ = _serve._SOURCE_SEQ
_maybe_profiler = _serve._maybe_profiler


class ServerOverloaded(RuntimeError):
    """The request queue is beyond max_queue: this request was shed
    immediately (fast-fail) instead of being queued into unbounded
    latency. Back off and retry, or add capacity."""


class DeadlineExceeded(TimeoutError):
    """The request's deadline_ms elapsed while it waited in the queue; it
    was never dispatched (no device work was wasted on it)."""


def _resolve(future, result=None, exc=None):
    """Resolve a request future, tolerating caller-side cancel(): queued
    futures are never marked running, so a client may cancel at any time —
    set_result/set_exception then raise InvalidStateError, which must not
    kill a worker thread or strand the batch's other requests."""
    try:
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(result)
    except Exception:
        pass


def shed_if_overloaded(stats, max_queue, fail, request_id=None):
    """Load-shedding check shared by BatchingPredictor and
    decoding.DecodingPredictor. The CALLER must hold stats._lock: the
    depth check and the enqueue increment form one critical section, or
    N concurrent submits at depth max_queue-1 would ALL pass and
    overshoot the bound by the submitter concurrency. Returns True when
    the request was shed (fail(exc) already called). `request_id` (a
    caller trace id) is named in the shed message and — on stats that
    keep one — appended to the tagged-failure trace (under the lock
    the caller already holds)."""
    if max_queue is not None and stats.queue_depth >= max_queue:
        stats.shed += 1
        if request_id is not None and hasattr(stats, '_failures'):
            stats._failures.append({'request_id': str(request_id),
                                    'kind': 'shed',
                                    'time': time.time()})
        fail(ServerOverloaded(
            'queue depth %d >= max_queue %d — request shed%s'
            % (stats.queue_depth, max_queue,
               ' (request %s)' % request_id if request_id else '')))
        return True
    return False


def select_bucket(buckets, rows):
    """Smallest compiled bucket that fits `rows` — deterministic for ANY
    bucket order. Loaders sort their bucket lists once at load (this
    class, decoding.DecodingPredictor) so the scan stays a prefix walk,
    but a caller handing an unsorted list still gets the smallest fit
    rather than the first fit (a hand-edited signature once returned the
    128-bucket for a 2-row batch). Raises if even the largest bucket is
    too small."""
    fit = [b for b in buckets if rows <= b]
    if fit:
        return min(fit)
    raise ValueError(
        "batch of %d rows exceeds the largest compiled bucket %d"
        % (rows, max(buckets)))


def _batch_rows(sig):
    """The artifact's dense batch dimension: the (required-uniform) leading
    dim of every dense feed."""
    lead = set()
    for e in sig['feeds']:
        if int(e.get('lod_levels', 0)):
            continue
        if not e['shape']:
            raise ValueError(
                "feed %r has no batch dimension (shape []); the batcher "
                "needs batch-led dense feeds" % e['name'])
        lead.add(int(e['shape'][0]))
    if len(lead) != 1:
        raise ValueError(
            "artifact feeds disagree on the batch dimension (%s); the "
            "batcher needs one uniform leading batch dim" % sorted(lead))
    return lead.pop()


class _Request(object):
    __slots__ = ('arrays', 'rows', 'future', 't_submit', 'deadline')

    def __init__(self, arrays, rows, future, deadline_ms=None):
        self.arrays = arrays
        self.rows = rows
        self.future = future
        self.t_submit = time.perf_counter()
        self.deadline = (self.t_submit + deadline_ms / 1e3
                         if deadline_ms is not None else None)


class ServingStats(object):
    """Thread-safe serving counters: queue-depth gauge, cumulative batch
    occupancy, and a sliding window of per-request latencies for
    percentile reporting."""

    def __init__(self, window=8192):
        self._lock = threading.Lock()
        self._lat = deque(maxlen=window)
        self.tier = 'bf16'   # serving tier of the source (bf16/int8)
        self.queue_depth = 0
        self.requests = 0
        self.batches = 0
        self.filled_rows = 0
        self.bucket_rows = 0
        self.shed = 0      # fast-failed at submit: queue beyond max_queue
        self.expired = 0   # deadline_ms elapsed while queued
        self.drained = 0   # shed by drain(): queued when scale-in began

    def reset(self):
        """Zero the counters and latency window (queue_depth is a live
        gauge and stays): separates a warmup/calibration phase from the
        measured run."""
        with self._lock:
            self._lat.clear()
            self.requests = 0
            self.batches = 0
            self.filled_rows = 0
            self.bucket_rows = 0
            self.shed = 0
            self.expired = 0
            self.drained = 0

    def record_batch(self, filled, bucket, latencies_s):
        with self._lock:
            self.batches += 1
            self.requests += len(latencies_s)
            self.filled_rows += filled
            self.bucket_rows += bucket
            self._lat.extend(latencies_s)

    def snapshot(self):
        """One consistent dict: queue_depth, requests, batches, occupancy
        (filled/bucket rows), p50/p95/p99_ms over the latency window."""
        with self._lock:
            lat = np.asarray(self._lat, np.float64) * 1e3
            snap = {'tier': self.tier,
                    'queue_depth': int(self.queue_depth),
                    'requests': int(self.requests),
                    'batches': int(self.batches),
                    'shed': int(self.shed),
                    'expired': int(self.expired),
                    'drained': int(self.drained),
                    'occupancy': round(self.filled_rows / self.bucket_rows, 4)
                    if self.bucket_rows else 0.0}
        if lat.size:
            p50, p95, p99 = np.percentile(lat, [50, 95, 99])
            snap.update(p50_ms=round(float(p50), 3),
                        p95_ms=round(float(p95), 3),
                        p99_ms=round(float(p99), 3))
        else:
            snap.update(p50_ms=0.0, p95_ms=0.0, p99_ms=0.0)
        return snap


class BatchingPredictor(object):
    """Coalesce concurrent requests into batched dispatches over a
    (multi-bucket) compiled artifact.

    submit(inputs) -> Future   enqueue one request (rows x feed shapes)
    run(inputs)                submit + wait (synchronous convenience)
    warmup()                   compile every bucket ahead of traffic
    stats.snapshot()           serving metrics (also via profiler report)
    close()                    drain the queue and stop worker threads

    `inputs` is a list (feed order) or dict of arrays whose leading dim is
    this request's row count (1..max_batch_size); trailing dims must match
    the artifact feeds. Dense feeds/fetches only — LoD serving keeps the
    one-artifact-per-bucket discipline of CompiledPredictor.
    """

    def __init__(self, artifact_dir, platform=None, max_batch_size=None,
                 batch_timeout_ms=5.0, inflight=2, stats_window=8192,
                 max_queue=None, tier=None):
        # tier resolution happens ONCE at the top (`tier='int8'` serves
        # the quantized tree); the per-bucket predictors below load from
        # inside the resolved tier, where no further subdir exists. The
        # profiler source keeps the ARTIFACT's name — the tier is its
        # own report column, not part of the identity
        display_dir = artifact_dir
        artifact_dir = _serve.resolve_tier(artifact_dir, tier)
        with open(os.path.join(artifact_dir, _serve._SIGNATURE)) as f:
            top_sig = json.load(f)
        self.tier = top_sig.get('tier', 'bf16')
        # lod rejection first: feeds are the same in every bucket, and
        # _batch_rows on an all-lod artifact would raise a misleading
        # "feeds disagree on the batch dimension" error
        for e in top_sig['feeds']:
            if int(e.get('lod_levels', 0)):
                raise ValueError(
                    "feed %r carries lod; the batcher serves dense feeds "
                    "only — export one artifact per lod bucket and serve "
                    "it with CompiledPredictor" % e['name'])
        sizes = top_sig.get('buckets')
        if sizes:
            preds = {int(b): _serve.CompiledPredictor(
                os.path.join(artifact_dir, _serve._BUCKET_DIR % int(b)),
                platform=platform) for b in sizes}
        else:  # single-bucket artifact (v1/v2 layout) — one bucket
            pred = _serve.CompiledPredictor(artifact_dir, platform=platform)
            preds = {_batch_rows(pred._sig): pred}
        self._buckets = sorted(preds)
        self._preds = preds
        self._sig = preds[self._buckets[-1]]._sig
        for b in self._buckets:
            for e in _serve._fetch_entries(preds[b]._sig):
                if int(e.get('lod_levels', 0)):
                    raise ValueError(
                        "fetch %r carries lod; the batcher cannot slice "
                        "per-request lod results" % e['name'])
                shape = e.get('shape')
                if shape is not None and (not shape or int(shape[0]) != b):
                    raise ValueError(
                        "fetch %r has shape %s in the %d-row bucket — not "
                        "batch-aligned, so per-request results cannot be "
                        "sliced back (e.g. a batch reduction); fetch "
                        "per-row outputs instead" % (e['name'], shape, b))
        # per-feed (name, trailing shape, dtype); batch dim is shape[0]
        _batch_rows(self._sig)  # validates uniform batch-led feeds
        self._feed_specs = [
            (e['name'], tuple(e['shape'][1:]), np.dtype(e['dtype']))
            for e in self._sig['feeds']]
        self._feed_names = [n for n, _, _ in self._feed_specs]
        largest = self._buckets[-1]
        self._max_rows = min(max_batch_size or largest, largest)
        self._timeout_s = max(batch_timeout_ms, 0.0) / 1e3
        # load-shedding bound: queued requests beyond this fast-fail with
        # ServerOverloaded instead of growing tail latency unboundedly
        # (every queued request behind a full device is pure added p99)
        self._max_queue = int(max_queue) if max_queue else None
        self._queue = queue.Queue()
        self._inflight = queue.Queue(maxsize=max(1, int(inflight)))
        self.stats = ServingStats(stats_window)
        self.stats.tier = self.tier
        self._closed = False
        self._draining = False
        # orders submit()'s closed-check+enqueue against close()'s
        # closed-set+_STOP: no request can land behind the sentinel
        self._lifecycle = threading.Lock()
        self._coalesce_t = threading.Thread(
            target=self._coalesce_loop, name='ptpu-batcher-coalesce',
            daemon=True)
        self._deliver_t = threading.Thread(
            target=self._deliver_loop, name='ptpu-batcher-deliver',
            daemon=True)
        self._coalesce_t.start()
        self._deliver_t.start()
        self._profiler_name = None
        prof = _maybe_profiler()
        if prof is not None and hasattr(prof, 'register_serving_source'):
            name = 'serving:%s#%d' % (
                os.path.basename(os.path.normpath(display_dir)),
                next(_SOURCE_SEQ))
            prof.register_serving_source(name, self.stats.snapshot)
            self._profiler_name = name

    # -- public API --------------------------------------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return [e['name'] for e in _serve._fetch_entries(self._sig)]

    @property
    def buckets(self):
        return list(self._buckets)

    def submit(self, inputs, deadline_ms=None, request_id=None):
        """Enqueue one request; returns a Future resolving to the list of
        per-fetch numpy arrays sliced to this request's rows. Validation
        errors fail THIS future only (a bad request never poisons a
        batch). With `deadline_ms`, a request still queued when the
        deadline elapses resolves to DeadlineExceeded instead of being
        dispatched late. When the queue is beyond `max_queue`, the future
        resolves to ServerOverloaded immediately — load is shed at the
        door, before any padding or device work. `request_id` is an
        optional caller trace id named in the shed message."""
        if self._closed:
            raise RuntimeError('BatchingPredictor is closed')
        fut = Future()

        def _shed_locked():
            return shed_if_overloaded(self.stats, self._max_queue,
                                      fut.set_exception,
                                      request_id=request_id)

        with self.stats._lock:     # fast-fail before validation work
            if _shed_locked():
                return fut
        try:
            arrays, rows = self._validate(inputs)
        except Exception as e:
            fut.set_exception(e)
            return fut
        with self._lifecycle:
            if self._closed:
                raise RuntimeError('BatchingPredictor is closed')
            with self.stats._lock:
                if _shed_locked():  # re-check atomically with the enqueue
                    return fut
                self.stats.queue_depth += 1
            self._queue.put(_Request(arrays, rows, fut, deadline_ms))
        return fut

    def run(self, inputs, timeout=None, deadline_ms=None):
        """Synchronous single-request path: submit + wait."""
        return self.submit(inputs, deadline_ms=deadline_ms).result(timeout)

    def warmup(self):
        """Compile every bucket ahead of traffic (the reference predictor's
        Prepare; CompiledPredictor.warmup analogue)."""
        for b in self._buckets:
            args = [np.zeros((b,) + trail, dtype)
                    for _, trail, dtype in self._feed_specs]
            for o in self._preds[b]._call_flat(args):
                np.asarray(o)
        return self

    def drain(self):
        """Draining stop for scale-in (the fleet router's hook): stop
        admitting (submit() raises), SHED the queued backlog loudly —
        each queued request resolves ServerOverloaded and is counted in
        both `shed` and `drained` (it was never dispatched, so a router
        can safely re-route it) — then wait for the in-flight dispatches
        to deliver and stop the worker threads. Contrast close(), which
        serves the backlog before stopping. Idempotent."""
        with self._lifecycle:
            self._draining = True
        self.close()

    def close(self):
        """Drain queued requests, stop worker threads, unregister metrics.
        Idempotent; submit() afterwards raises."""
        with self._lifecycle:
            if self._closed:
                return
            self._closed = True
            self._queue.put(_STOP)
        self._coalesce_t.join()
        while True:  # safety net; the lifecycle lock should make this dead
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            if req is not _STOP:
                with self.stats._lock:
                    self.stats.queue_depth -= 1
                _resolve(req.future,
                         exc=RuntimeError('BatchingPredictor closed'))
        self._inflight.put(_STOP)
        self._deliver_t.join()
        if self._profiler_name:
            prof = _maybe_profiler()
            if prof is not None:
                prof.unregister_serving_source(self._profiler_name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- internals ---------------------------------------------------------
    def _validate(self, inputs):
        if isinstance(inputs, (list, tuple)):
            if len(inputs) != len(self._feed_names):
                raise ValueError(
                    "batcher expects %d inputs (%s), got %d"
                    % (len(self._feed_names), self._feed_names, len(inputs)))
            feed = dict(zip(self._feed_names, inputs))
        else:
            feed = dict(inputs)
        missing = [n for n in self._feed_names if n not in feed]
        if missing:
            raise ValueError("missing feeds: %r (artifact expects %s)"
                             % (missing, self._feed_names))
        arrays, rows = [], None
        for name, trail, dtype in self._feed_specs:
            value = feed[name]
            arr = np.asarray(value, dtype=dtype)
            if arr is value:
                # snapshot the caller's own buffer: dispatch is async, and
                # a client reusing its buffer for the next request must
                # not corrupt this one (the bit-identity contract)
                arr = arr.copy()
            if arr.ndim != len(trail) + 1 or tuple(arr.shape[1:]) != trail:
                raise ValueError(
                    "feed %r: expected per-request shape [rows]+%s, got %s"
                    % (name, list(trail), list(arr.shape)))
            if rows is None:
                rows = arr.shape[0]
            elif arr.shape[0] != rows:
                raise ValueError(
                    "feeds disagree on request rows: %r has %d, expected %d"
                    % (name, arr.shape[0], rows))
            arrays.append(arr)
        if not rows:
            raise ValueError("empty request (0 rows)")
        if rows > self._max_rows:
            raise ValueError(
                "request of %d rows exceeds max_batch_size %d"
                % (rows, self._max_rows))
        return arrays, rows

    def _reap_expired(self, req):
        """Resolve a request whose deadline elapsed in the queue; True
        when reaped (it must not join a batch)."""
        if req.deadline is None or time.perf_counter() <= req.deadline:
            return False
        with self.stats._lock:
            self.stats.queue_depth -= 1
            self.stats.expired += 1
        _resolve(req.future, exc=DeadlineExceeded(
            'request expired after %.1f ms in queue (deadline_ms=%.1f)'
            % ((time.perf_counter() - req.t_submit) * 1e3,
               (req.deadline - req.t_submit) * 1e3)))
        return True

    def _shed_drained(self, req):
        """drain() in progress: a still-queued request sheds loudly
        (ServerOverloaded; shed+drained counters) instead of joining a
        batch — it never cost device work, so a fleet router can
        re-route it to another replica."""
        with self.stats._lock:
            self.stats.queue_depth -= 1
            self.stats.shed += 1
            self.stats.drained += 1
        _resolve(req.future, exc=ServerOverloaded(
            'request shed: predictor draining for scale-in'))

    def _coalesce_loop(self):
        carry = None
        while True:
            req = carry if carry is not None else self._queue.get()
            carry = None
            if req is _STOP:
                return
            if self._draining:
                self._shed_drained(req)
                continue
            if self._reap_expired(req):
                continue
            batch, rows = [req], req.rows
            deadline = time.perf_counter() + self._timeout_s
            while rows < self._max_rows:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    nxt = self._queue.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is _STOP:
                    carry = _STOP  # dispatch this batch, then stop
                    break
                if self._draining:
                    self._shed_drained(nxt)
                    continue
                if self._reap_expired(nxt):
                    continue
                if rows + nxt.rows > self._max_rows:
                    carry = nxt  # seed the next batch
                    break
                batch.append(nxt)
                rows += nxt.rows
            self._dispatch(batch, rows)

    def _dispatch(self, batch, rows):
        with self.stats._lock:
            self.stats.queue_depth -= len(batch)
        try:
            bs = select_bucket(self._buckets, rows)
            args = []
            for i, (_, trail, dtype) in enumerate(self._feed_specs):
                parts = [r.arrays[i] for r in batch]
                if rows < bs:
                    parts.append(np.zeros((bs - rows,) + trail, dtype))
                args.append(parts[0] if len(parts) == 1
                            else np.concatenate(parts, axis=0))
            outs = self._preds[bs]._call_flat(args)  # async: no sync here
        except Exception as e:
            for r in batch:
                _resolve(r.future, exc=e)
            return
        # hand off while the device (or XLA:CPU thread pool) executes; the
        # bounded queue is the double-buffer backpressure — at most
        # `inflight` batches ahead of delivery
        self._inflight.put((batch, rows, bs, outs))

    def _deliver_loop(self):
        while True:
            item = self._inflight.get()
            if item is _STOP:
                return
            batch, rows, bs, outs = item
            try:
                outs = [np.asarray(o) for o in outs]  # block_until_ready
                for e, o in zip(_serve._fetch_entries(self._sig), outs):
                    # runtime guard for v2 artifacts whose signatures do
                    # not record fetch shapes (load-time check impossible)
                    if o.ndim < 1 or o.shape[0] != bs:
                        raise ValueError(
                            "fetch %r has shape %s from the %d-row bucket "
                            "— not batch-aligned, per-request slicing is "
                            "impossible" % (e['name'], list(o.shape), bs))
            except Exception as e:
                for r in batch:
                    _resolve(r.future, exc=e)
                continue
            # record stats BEFORE resolving: a caller reading
            # stats.snapshot() right after result() returns must see this
            # batch accounted
            now = time.perf_counter()
            self.stats.record_batch(rows, bs,
                                    [now - r.t_submit for r in batch])
            off = 0
            for r in batch:
                _resolve(r.future, [o[off:off + r.rows] for o in outs])
                off += r.rows


def load_batching(artifact_dir, **kwargs):
    return BatchingPredictor(artifact_dir, **kwargs)
