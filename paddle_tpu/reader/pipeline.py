"""Host→device input pipeline: the py_reader / double_buffer equivalent
(ref: fluid/layers/io.py:633 py_reader, :1002 double_buffer,
operators/reader/buffered_reader.cc, lod_tensor_blocking_queue.h).

A feeding thread converts python batches and stages them to the device
(double-buffer prefetch); the executor pops a staged batch when the program's
data vars are not covered by an explicit feed. EOF surfaces as
fluid.core.EOFException exactly like the reference (read_op throws on a
closed queue).

`prefetch_to_device(steps)` upgrades the per-batch queue to a STAGED GROUP
RING for multi-step dispatch (Executor.run_steps): the feeder thread
stacks `steps` host batches into one [K, ...] device buffer per feed var
while the previous K-step program executes — one device transfer per K
steps, double-buffered by queue depth. EOF flushes a partial tail group
(m < K) for the consumer's smaller compiled bucket.
"""
from __future__ import annotations

import queue as _q
import threading
import time as _time

import numpy as np

from ..core import EOFException
from ..framework import default_main_program


class PyReader(object):
    def __init__(self, feed_vars, capacity, use_double_buffer=True,
                 feed_converter=None):
        self.feed_vars = feed_vars
        self.var_names = [v.name for v in feed_vars]
        self.capacity = capacity
        self.use_double_buffer = use_double_buffer
        self._queue = _q.Queue(maxsize=capacity)
        self._feeder_fn = None
        self._thread = None
        self._closed = True
        self._exc = None
        self._converter = feed_converter
        self._source = None
        self._data_feeder = None
        self._feeder_registered = False
        self._prefetch_k = None
        self._prefetch_depth = 2
        self._mode_k = 0        # group size the LAST start() ran with
        self._pending_eof = False
        self.prefetch_stats = {'groups': 0, 'tail_groups': 0,
                               'stage_s': 0.0}
        self._stage_s_total = 0.0   # lifetime staging s across epochs

    def prefetch_to_device(self, steps, depth=2):
        """Stage fixed groups of `steps` stacked batches to the device.

        The feeder thread accumulates `steps` host batches, stacks them
        into one [steps, ...] buffer per feed var, and stages the stack
        with ONE device_put per var — while the consumer's previous
        K-step dispatch (Executor.run_steps) executes. `depth` is the
        number of staged groups the ring holds (2 = double buffering: the
        next group stages under the current group's execution). At EOF a
        partial tail group (fewer than `steps` batches) is flushed so the
        consumer can run it through a smaller compiled bucket. Dense
        ndarray feeds only — LoD batches have per-batch offsets that
        cannot stack into one ring buffer (bucket + pad first).

        Returns self (chainable); takes effect at the next start()."""
        steps = int(steps)
        if steps < 1:
            raise ValueError("prefetch_to_device: steps must be >= 1, "
                             "got %d" % steps)
        if int(depth) < 1:
            raise ValueError("prefetch_to_device: depth must be >= 1, "
                             "got %d" % int(depth))
        self._prefetch_k = steps
        self._prefetch_depth = int(depth)
        return self

    # -- graph side --------------------------------------------------------
    def read(self):
        """Returns the data vars (the read_file() surface)."""
        return list(self.feed_vars)

    # -- host side ---------------------------------------------------------
    def decorate_paddle_reader(self, reader, places=None):
        from ..data_feeder import DataFeeder
        feeder = DataFeeder(self.feed_vars, program=None) \
            if self._converter is None else None
        self._source = reader       # a pooled reader exposes feeder_stats
        self._data_feeder = feeder  # row->array convert time rides along

        def fn():
            for batch in reader():
                if feeder is not None:
                    yield feeder.feed(batch)
                else:
                    yield self._converter(batch)
        self._feeder_fn = fn

    decorate_sample_list_generator = decorate_paddle_reader

    def decorate_tensor_provider(self, reader, places=None):
        self._source = reader
        self._data_feeder = None

        def fn():
            for batch in reader():
                if isinstance(batch, dict):
                    yield batch
                else:
                    yield dict(zip(self.var_names, batch))
        self._feeder_fn = fn

    decorate_batch_generator = decorate_tensor_provider

    def _register_feeder_source(self):
        """Surface this reader's feeder-side counters (decode pool stats
        when the decorated reader is a sharded/pooled one, plus ring
        staging time and queue depth) in profiler.training_report()."""
        if self._feeder_registered:
            return
        self._feeder_registered = True
        import weakref
        from .. import profiler as _profiler
        ref = weakref.ref(self)
        name = 'pyreader@%x' % id(self)

        def snap():
            rd = ref()
            if rd is None:
                _profiler.unregister_feeder_source(name)
                raise ReferenceError('py_reader collected')
            out = {}
            src_stats = getattr(rd._source, 'feeder_stats', None)
            if callable(src_stats):
                out.update(src_stats())
            out['stage_ms'] = (rd._stage_s_total
                               + rd.prefetch_stats['stage_s']) * 1e3
            try:
                out['ring_depth'] = rd._queue.qsize()
            except Exception:
                out['ring_depth'] = 0
            df = rd._data_feeder
            if df is not None:
                out['convert_ms'] = df.convert_s * 1e3
            return out
        _profiler.register_feeder_source(name, snap)

    def start(self):
        assert self._feeder_fn is not None, (
            "call decorate_paddle_reader/decorate_tensor_provider first")
        self._closed = False
        self._exc = None
        self._pending_eof = False  # a consumer-side tail-flush marker
        # snapshot the mode: prefetch_to_device takes effect HERE, not
        # mid-epoch (the pop guards check what this start() staged)
        self._mode_k = self._prefetch_k or 0
        if self._mode_k:
            self._queue = _q.Queue(maxsize=self._prefetch_depth)
            # prefetch_stats is per-epoch; fold the finished epoch's
            # staging time into the lifetime accumulator first so the
            # feeder table's stage(ms) shares a time base with the
            # cumulative samples/decode/convert columns
            self._stage_s_total += self.prefetch_stats['stage_s']
            self.prefetch_stats = {'groups': 0, 'tail_groups': 0,
                                   'stage_s': 0.0}
            target = self._prefetch_work
        else:
            self._queue = _q.Queue(maxsize=self.capacity)
            target = self._work
        # the worker captures ITS epoch's queue: a stale thread that
        # outlives a mid-epoch reset()+start() (join timed out, or it was
        # inside a device_put) can only ever write to its own dead queue,
        # never interleave into the new epoch's
        self._thread = threading.Thread(target=target, args=(self._queue,),
                                        daemon=True)
        self._thread.start()
        self._register_feeder_source()

    def _work(self, q):
        try:
            import jax
            for feed in self._feeder_fn():
                if self._closed or self._queue is not q:
                    return
                if self.use_double_buffer:
                    # stage to device from the feeding thread so the
                    # consumer finds data already resident (the
                    # double_buffer/buffered_reader prefetch)
                    feed = {k: (v if not isinstance(v, np.ndarray)
                                else jax.device_put(v))
                            for k, v in feed.items()}
                q.put(feed)
            q.put(_EOF)
        except Exception as e:  # surface in consumer
            if self._queue is q:  # a stale thread must not poison the
                self._exc = e     # NEW epoch's error slot
            q.put(_EOF)

    def _stage_group(self, group, stats):
        """Stack a list of host batches into one [k, ...] buffer per feed
        var and stage it — the ring's unit of transfer is one device_put
        per var per K steps instead of K. `stats` is the OWNING epoch's
        counter dict, captured at thread start (a stale thread surviving
        a mid-epoch reset must not bump the new epoch's counters)."""
        import jax
        t0 = _time.perf_counter()
        out = {}
        for name in group[0]:
            vals = [b[name] for b in group]
            if any(not isinstance(v, (np.ndarray, jax.Array))
                   for v in vals):
                raise TypeError(
                    "prefetch_to_device stages dense ndarray feeds only; "
                    "feed %r is %s — LoD/structured batches carry "
                    "per-batch offsets that cannot stack into one "
                    "[K, ...] ring buffer (bucket + pad first)"
                    % (name, type(vals[0]).__name__))
            shapes = {np.shape(v) for v in vals}
            if len(shapes) != 1:
                raise ValueError(
                    "prefetch_to_device: feed %r batch shapes differ "
                    "within a group (%s) — pad/bucket the reader so every "
                    "group stacks to one [K, ...] buffer"
                    % (name, sorted(shapes)))
            if any(isinstance(v, jax.Array) for v in vals):
                # already-on-device batches: stack device-side — pulling
                # them to host first would cost K D2H round-trips per
                # group
                import jax.numpy as jnp
                out[name] = jnp.stack(vals)
                continue
            stacked = np.stack(vals)
            out[name] = (jax.device_put(stacked) if self.use_double_buffer
                         else stacked)
        stats['stage_s'] += _time.perf_counter() - t0
        return out, len(group)

    def _prefetch_work(self, q):
        stats = self.prefetch_stats  # this epoch's counters, captured
        try:
            group = []
            for feed in self._feeder_fn():
                if self._closed or self._queue is not q:
                    return
                group.append(feed)
                if len(group) == self._mode_k:
                    q.put(self._stage_group(group, stats))
                    stats['groups'] += 1
                    group = []
            if group:  # EOF mid-group: flush the partial tail
                q.put(self._stage_group(group, stats))
                stats['groups'] += 1
                stats['tail_groups'] += 1
            q.put(_EOF)
        except Exception as e:  # surface in consumer
            if self._queue is q:  # a stale thread must not poison the
                self._exc = e     # NEW epoch's error slot
            q.put(_EOF)

    def reset(self):
        self._closed = True
        self._pending_eof = False
        try:
            while True:
                self._queue.get_nowait()
        except _q.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5)
        self._thread = None

    def _next_batch(self):
        if self._mode_k:
            raise RuntimeError(
                "py_reader was started in prefetch_to_device mode (staged "
                "[K, ...] groups): drive it with Executor.run_steps, or "
                "drop the prefetch_to_device call before start()")
        return self._pop()

    def _next_group(self):
        """Pop one staged group: ({name: [k, ...] stacked value}, k).
        k is smaller than the configured group size only for the EOF tail
        flush; EOFException raises when the epoch is drained (read_op
        semantics, like _next_batch)."""
        if self._prefetch_k is None and not self._mode_k:
            raise RuntimeError(
                "py_reader is not in prefetch mode: call "
                "prefetch_to_device(steps) before start()")
        if not self._mode_k:
            if self._thread is None:
                raise EOFException("py_reader not started")
            raise RuntimeError(
                "py_reader was started in per-batch mode; "
                "prefetch_to_device takes effect at the next start()")
        return self._pop()

    def _pop(self):
        if self._thread is None and self._closed:
            raise EOFException("py_reader not started")
        item = self._queue.get()
        if item is _EOF:
            self._closed = True
            # rejoin the feeder HERE, not only at reset(): the thread has
            # already queued _EOF and is exiting, so the join is
            # immediate — and a caller that loops sessions without ever
            # calling reset() (the parallel/api.py iter_epoch pattern)
            # no longer accumulates one dead Thread object per epoch
            t = self._thread
            self._thread = None
            if t is not None:
                t.join(timeout=5)
            if self._exc is not None:
                raise self._exc
            raise EOFException("py_reader reached end of data")
        return item


class _EOFSentinel(object):
    pass


_EOF = _EOFSentinel()
