"""Runner 'train_loop': a trainer's hot path on a resident batch.

Per step one Executor.run (or ParallelExecutor.run) with
return_numpy=False, in groups of `group_steps`; at most `groups_in_flight`
groups are enqueued ahead of the last loss the host has read, so the host
always knows how far the device is (bench.py:_timed_steps' async loop with
a bounded queue). Steps count when their group's last loss has been read;
the window runs from one such read to the first one after --seconds.

Nothing here knows a model: the program, its feed and the reference it is
held to come from the configuration's module (benchmark/configs/<model>.py:
build, make_feed, reference), what is compared and how closely from the
configuration file's "verify".

Traffic file fields: runner, executor ('single' | 'parallel'),
batch_per_chip, group_steps, groups_in_flight, warmup_groups,
trace_seconds.
"""
from __future__ import annotations

import collections
import math
import time

import numpy as np

from ..harness import say


def _distance(kind, got, want):
    """How far the program's value is from the reference's."""
    got = got.reshape(want.shape)
    if kind == 'abs':
        return float(np.max(np.abs(got - want)))
    if kind == 'rel_l2':
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))
    raise ValueError('unknown comparison %r' % kind)


class Runner(object):
    def __init__(self, ctx):
        self.ctx = ctx
        self.parallel = ctx.traffic['executor'] == 'parallel'
        self.batch = int(ctx.traffic['batch_per_chip']) * ctx.chips
        self.group = int(ctx.traffic['group_steps'])
        self.in_flight = int(ctx.traffic['groups_in_flight'])
        self.result = {}

    # -- set-up ----------------------------------------------------------
    def setup(self):
        import paddle_tpu as fluid
        from paddle_tpu.core import compile_cache
        ctx = self.ctx
        self.fluid = fluid
        self.main, self.startup, self.fetches = ctx.model.build(ctx.cfg)
        self.loss = self.fetches['loss']
        self.scope = fluid.core.Scope()
        self.feed = self._make_feed(self.batch, ctx.seed)
        with fluid.scope_guard(self.scope):
            fluid.Executor().run(self.startup)
        self.exe = self._executor(self.main, self.scope, [self.loss])
        c0 = compile_cache.stats()
        for _ in range(max(int(ctx.traffic.get('warmup_groups', 1)), 1)):
            last = None
            for _ in range(self.group):
                last = self._step()
            self._read(last)
        say('train warm-up done', batch=self.batch,
            exec_tier_hits=compile_cache.stats()['exec_hits']
            - c0['exec_hits'])

    def _executor(self, program, scope, fetch):
        """A callable feed -> [array per variable of `fetch`] running one
        step of `program`."""
        fluid = self.fluid
        if self.parallel:
            pe = fluid.ParallelExecutor(use_cuda=False,
                                        loss_name=self.loss.name,
                                        main_program=program, scope=scope)
            if pe.device_count < self.ctx.chips:
                raise RuntimeError('mesh has %d devices, the cell needs %d'
                                   % (pe.device_count, self.ctx.chips))
            names = [v.name for v in fetch]
            return lambda feed: pe.run(names, feed=feed, return_numpy=False)
        exe = fluid.Executor()

        def run(feed):
            with fluid.scope_guard(scope):
                return exe.run(program, feed=feed, fetch_list=fetch,
                               return_numpy=False)
        return run

    def _make_feed(self, batch, seed):
        """The configuration's own feed for one batch, made on the device
        from the seed; on several chips already placed batch-sharded on
        the mesh ParallelExecutor builds, so that no step copies it."""
        shard = None
        if self.parallel:
            from paddle_tpu.parallel.mesh import make_mesh, batch_sharded
            mesh = make_mesh()

            def shard(ndim):
                return batch_sharded(mesh, ndim)
        return self.ctx.model.make_feed(self.ctx.cfg, batch, seed, shard)

    def _step(self):
        with self.ctx.spans.span('exe_run'):
            return self.exe(self.feed)[0]

    def _read(self, loss):
        with self.ctx.spans.span('sync'):
            return float(np.asarray(loss).reshape(-1)[0])

    # -- the measured window -----------------------------------------------
    def window(self, seconds):
        """Runs the loop for `seconds`; with tracing on, the last
        trace_seconds of it are traced and the rates come from the part
        before."""
        from paddle_tpu.core import compile_cache
        ctx = self.ctx
        traced_s = ctx.trace_seconds() if ctx.trace else 0.0
        pending = collections.deque()
        losses = []
        c0 = compile_cache.stats()
        # fill the queue, then open the window at a loss read
        for _ in range(self.in_flight):
            pending.append(self._enqueue_group(losses))
        self._read(pending.popleft()[-1])
        t_open = time.perf_counter()
        steps = 0
        t_rate_end, steps_rate = None, 0
        tracing = False
        while True:
            pending.append(self._enqueue_group(losses))
            self._read(pending.popleft()[-1])
            steps += self.group
            now = time.perf_counter()
            if ctx.trace and not tracing and \
                    now - t_open >= seconds - traced_s:
                t_rate_end, steps_rate = now, steps
                ctx.tracer.start()
                tracing = True
                continue
            if now - t_open >= seconds:
                t_close = now
                break
        ctx.tracer.stop()
        while pending:                      # drain what is still queued
            self._read(pending.popleft()[-1])
        c1 = compile_cache.stats()
        if t_rate_end is None:
            t_rate_end, steps_rate = t_close, steps
        vals = [float(np.asarray(l).reshape(-1)[0]) for l in losses]
        self.result = {
            'window_s': t_rate_end - t_open,
            'steps': steps_rate,
            'samples_per_s': steps_rate * self.batch / (t_rate_end - t_open),
            'losses_finite': all(math.isfinite(v) for v in vals),
            'first_loss': vals[0], 'last_loss': vals[-1],
            'compiles_in_window':
                c1['xla_compiles_net'] - c0['xla_compiles_net'],
            't_open': t_open, 't_close': t_close,
            'attempted': steps, 'failed': 0,
            # what the configuration's step_floor_seconds is asked about
            'floor_arg': int(ctx.traffic['batch_per_chip']),
        }
        say('train window', steps=steps_rate,
            seconds=self.result['window_s'],
            samples_per_s=self.result['samples_per_s'],
            first_loss=vals[0], last_loss=vals[-1])
        return self.result

    def _enqueue_group(self, losses):
        group = [self._step() for _ in range(self.group)]
        losses.extend(group)
        return group

    # -- correctness, outside the window -------------------------------------
    def verify(self):
        """One step at the verify batch in a scope of its own, from the
        same weights_seed, against the configuration's plain float32
        reference given the same initial weights and the same seeded feed.
        What is compared, how and how closely is the configuration file's
        "verify.compare": {fetched name: {"kind": "abs" | "rel_l2",
        "tol": t}}."""
        ctx, fluid = self.ctx, self.fluid
        v = ctx.cfg['verify']
        n = int(v['batch'])
        if self.parallel:
            n = max(n, ctx.chips) // ctx.chips * ctx.chips
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor().run(self.startup)
        weights = {name: np.asarray(scope.get(name))
                   for name in scope.local_var_names()
                   if hasattr(scope.get(name), 'shape')}
        feed = self._make_feed(n, ctx.seed + 1)
        names = sorted(v['compare'])
        got = self._executor(self.main, scope,
                             [self.fetches[k] for k in names])(feed)
        want = ctx.model.reference(
            ctx.cfg, weights, {k: np.asarray(x) for k, x in feed.items()})
        ok = True
        for name, g in zip(names, got):
            spec = v['compare'][name]
            err = _distance(spec['kind'], np.asarray(g, np.float32),
                            np.asarray(want[name], np.float32))
            good = bool(err <= float(spec['tol']))   # a nan is not good
            ok = ok and good
            say('train verify ' + name, kind=spec['kind'], error=err,
                tol=float(spec['tol']), ok=good)
        r = self.result
        if not r.get('losses_finite'):
            say('train verify: a loss in the window was not finite')
        if r.get('compiles_in_window'):
            say('train verify: compiled inside the window',
                n=r['compiles_in_window'])
        return bool(ok and r.get('losses_finite')
                    and not r.get('compiles_in_window'))

    def close(self):
        pass

