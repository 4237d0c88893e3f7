"""Executor: trace-once/compile-once/run-many program execution.

Replaces the reference's interpret-per-step C++ Executor
(framework/executor.cc:203, python/paddle/fluid/executor.py:260). `run`
keeps the reference's feed/fetch contract, but under the hood the program
block is traced into a pure step function
    (state, feed, rng) -> (fetches, new_state)
jit-compiled by XLA, and cached keyed on (program, feed signature, fetch
names, state signature) — the moral equivalent of executor.py:222's program
cache, except a cache hit here skips ALL per-op work, not just op creation.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import framework
from .framework import Program, Variable, default_main_program, place_device
from .core.scope import Scope, global_scope, scope_guard  # re-export
from .core.lowering import Tracer, TraceError
from .core.lod import LoDArray, unwrap
from .core import amp
from .profiler import span as _span


import contextlib


def _nullcontext():
    return contextlib.nullcontext()


def _fetch_name(f):
    if isinstance(f, Variable):
        return f.name
    if isinstance(f, str):
        return f
    raise TypeError("fetch_list entries must be Variable or str, got %r" % (f,))


# module-level caches indexed BY PROGRAM UID (one slot per uid holding the
# live build epoch): a lookup miss invalidates only this program's stale
# entries in O(per-uid entries), never a scan of every program's keys
_analysis_cache = {}   # uid -> ((build_epoch, op_count), analysis)
_verify_cache = {}     # uid -> (build_epoch, {(feeds, fetches): errors})
_entropy_seed = None


def _np_threefry2x32(k0, k1, c0, c1):
    """Vectorized numpy Threefry-2x32 — bit-identical to jax's
    threefry2x32 for the same key/count words (validated against the jax
    cpu derivation in tests/test_multi_step.py). Used when no cpu backend
    is registered (JAX_PLATFORMS=tpu), where the host-side key derivation
    below would otherwise raise (ADVICE r5 item 3)."""
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    with np.errstate(over='ignore'):
        ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
        x0 = c0 + ks[0]
        x1 = c1 + ks[1]
        for i in range(5):
            for r in rot[i % 2]:
                x0 = x0 + x1
                x1 = (x1 << np.uint32(r)) | (x1 >> np.uint32(32 - r))
                x1 = x0 ^ x1
            x0 = x0 + ks[(i + 1) % 3]
            x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def _np_threefry_key_words(seed):
    """key(seed)'s two uint32 words, mirroring jax's seed
    canonicalization: with x64 disabled (the default) a python int seed
    becomes int32, so the upper word is ZERO — keeping `seed >> 32` there
    would derive a different stream than the jax-present path for seeds
    >= 2^32 and break the fallback's bit-identity contract."""
    seed = int(seed)
    if jax.config.jax_enable_x64:
        hi = np.uint32((seed >> 32) & 0xFFFFFFFF)
    else:
        hi = np.uint32(0)
    return hi, np.uint32(seed & 0xFFFFFFFF)


def _np_threefry_key_group(seed, step0, k):
    """fold_in(key(seed), step) raw key data for steps [step0, step0+k)
    with numpy only: fold_in computes threefry2x32(key, [0, step])."""
    hi, lo = _np_threefry_key_words(seed)
    k0 = np.full((k,), hi)
    k1 = np.full((k,), lo)
    steps = np.arange(step0, step0 + k, dtype=np.uint32)
    x0, x1 = _np_threefry2x32(k0, k1, np.zeros_like(steps), steps)
    return np.stack([x0, x1], axis=1)


# jitted once: derive the whole dispatch group's keys in ONE host-side
# executable instead of k eager fold_in chains
_FOLD_KEYS = None


def _fold_keys(base, steps):
    global _FOLD_KEYS
    if _FOLD_KEYS is None:
        _FOLD_KEYS = jax.jit(lambda b, s: jax.vmap(
            lambda i: jax.random.key_data(jax.random.fold_in(b, i)))(s))
    return _FOLD_KEYS(base, steps)


def _process_entropy():
    """Random seed root drawn once per JOB (used when a program has no
    random_seed and FLAGS deterministic is off). Under multi-host, every
    process must share the root — the SPMD program's replicated values are
    only replicated if every host computes them from the same seed — so
    process 0's draw is broadcast."""
    global _entropy_seed
    if _entropy_seed is None:
        import os as _os
        seed = int.from_bytes(_os.urandom(4), 'little') or 1
        try:
            nproc = jax.process_count()
        except RuntimeError:
            nproc = 1
        if nproc > 1:
            from jax.experimental import multihost_utils
            seed = int(np.asarray(multihost_utils.broadcast_one_to_all(
                np.uint32(seed))))
        _entropy_seed = seed or 1
    return _entropy_seed


def _verify_before_run(program, feed_names, fetch_names):
    """Fast static lint before the analysis cache (passes/verifier.py):
    warn-only by default — one RuntimeWarning per (program epoch, feed,
    fetch) signature — while PTPU_STRICT_VERIFY=1 raises
    ProgramVerifyError instead of letting the tracer fail opaquely."""
    from .passes import verifier as _verifier
    uid, epoch = program._uid, program._build_epoch
    sig = (frozenset(feed_names), tuple(fetch_names))
    cached = _verify_cache.get(uid)
    if cached is None or cached[0] != epoch:   # epoch turned: old sigs die
        cached = (epoch, {})
        _verify_cache[uid] = cached
    errs = cached[1].get(sig)
    if errs is None:
        diags = _verifier.verify_program(program, feed_names=feed_names,
                                         fetch_names=fetch_names,
                                         level='fast')
        errs = [d for d in diags if d.level == 'error']
        cached[1][sig] = errs
    if errs:
        _verifier.maybe_raise_or_warn(errs, warned_key=(uid, epoch) + sig)


def _program_analysis(program):
    """(persistable names, persistable∩written) — memoized per build epoch."""
    key = (program._build_epoch, sum(len(b.ops) for b in program.blocks))
    hit = _analysis_cache.get(program._uid)
    if hit is not None and hit[0] == key:
        return hit[1]
    persist = {v.name for v in program.list_vars() if v.persistable}
    written = set()
    for b in program.blocks:
        for op in b.ops:
            written.update(op.output_arg_names())
    out = (tuple(sorted(persist)), tuple(sorted(persist & written)))
    _analysis_cache[program._uid] = (key, out)
    return out


def _step_name(program, multi=False):
    """The jitted step's name — what a device trace's 'XLA Modules' line
    prints as jit_<name>: 'train_step' for a program with a backward pass,
    'program_step' for any other (startup, inference); '..._steps' for the
    K-step scan of run_steps."""
    train = any(op.type.endswith('_grad')
                for b in program.blocks for op in b.ops)
    return ('train_step' if train else 'program_step') + ('s' if multi
                                                          else '')


class Executor(object):
    def __init__(self, place=None):
        self.place = place
        # always one concrete device (framework.place_device): a place
        # whose backend is absent raises here, it never runs elsewhere
        self._device = place_device(place)
        self._cache = {}
        # uid -> set of _cache keys: keeps per-miss stale-epoch eviction
        # O(this program's entries) instead of a full-cache scan
        self._cache_index = {}
        self._step_counters = {}
        # multi-step dispatch counters (profiler.training_report contract;
        # an executor owned by an inference Predictor sets _profile_role =
        # 'infer' and the same counters surface as a bulk-infer source —
        # steps relabel as batches)
        self._dispatch_stats = {'dispatches': 0, 'steps': 0,
                                'tail_flushes': 0, 'host_stall_s': 0.0,
                                'ckpt_stall_s': 0.0, 'run_s': 0.0}
        self._profile_role = 'training'
        self._prof_registered = False
        # program uid -> last DonationCertificate (passes/dataflow.py)
        self._donation_certs = {}
        # id(array) -> array: state leaves OUR donated dispatches
        # produced — the only buffers provably XLA-owned and therefore
        # safe to donate through a RELOADED executable (everything else
        # may be a zero-copy view of host memory: device_put of numpy,
        # jnp.asarray over a checkpoint payload). Donation kills each
        # generation's buffers, so the retained entries are tiny dead
        # shells; the cap is a leak backstop, not a working set.
        self._owned_out = {}

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, feed_var_name='feed',
            fetch_var_name='fetch', scope=None, return_numpy=True,
            use_program_cache=True, checkpoint=None):
        # spans (profiler.span; inert unless a jax profiler trace runs):
        # exe/run > exe/feed, exe/prepare, exe/build (cache miss only),
        # exe/rng, exe/dispatch (> exe/place on a mesh), exe/finish
        with _span('exe/run') as sp:
            return self._run(sp, program, feed, fetch_list, scope,
                             return_numpy, checkpoint)

    def _run(self, sp, program, feed, fetch_list, scope, return_numpy,
             checkpoint):
        import time as _time
        t_run = _time.perf_counter() if checkpoint is not None else None
        program = program if program is not None else default_main_program()
        fetch_list = fetch_list or []
        if isinstance(fetch_list, (Variable, str)):
            fetch_list = [fetch_list]
        fetch_names = [_fetch_name(f) for f in fetch_list]
        mesh = None
        if hasattr(program, '_ptpu_compiled_program'):
            compiled = program
            mesh = compiled._get_mesh(self)
            # the pass-optimized clone for THIS fetch set (memoized);
            # falls back to the raw program if the pipeline declines
            program = compiled._optimized_program(fetch_names)
            if program is not compiled._program:
                # one rng/step stream per SOURCE program: the clone's own
                # uid would fork the counter per fetch set, and a
                # checkpoint restored against the raw program would never
                # reach it (core/checkpoint._program_uid contract)
                program._ptpu_counter_uid = getattr(
                    compiled._program, '_ptpu_counter_uid',
                    compiled._program._uid)
        scope = scope if scope is not None else global_scope()
        feed = feed or {}

        feed_vals = {}
        with _span('exe/feed', n=len(feed)):
            for name, value in feed.items():
                feed_vals[name] = self._to_device_value(
                    value, self._feed_var(program, name))

            # py_reader path: pull a staged batch for data vars not
            # explicitly fed
            for reader in getattr(program, '_py_readers', []):
                if not all(n in feed_vals for n in reader.var_names):
                    batch = reader._next_batch()  # raises EOFException
                    for n, v in batch.items():
                        if n not in feed_vals:
                            feed_vals[n] = self._to_device_value(
                                v, self._feed_var(program, n))

        with _span('exe/prepare'):
            # static lint (warn-only; PTPU_STRICT_VERIFY=1 raises) before
            # the analysis cache — malformed programs fail loudly at
            # build time
            _verify_before_run(program, set(feed_vals), fetch_names)

            # persistable state present in scope
            state, persist_written, out_state_names = self._gather_state(
                program, scope)

            mesh_key = (tuple(mesh.shape.items()) if mesh is not None
                        else None)
            key = self._cache_key(program, feed_vals, fetch_names, state,
                                  out_state_names) + (mesh_key,)
            fn = self._cache.get(key)
        if fn is None:
            with _span('exe/build'):
                self._evict_stale(program)
                fn = self._build(program, tuple(sorted(feed_vals)),
                                 tuple(fetch_names), tuple(sorted(state)),
                                 out_state_names, mesh, feed_vals)
                self._cache[key] = fn
                self._cache_index.setdefault(program._uid, set()).add(key)

        counter_uid = getattr(program, '_ptpu_counter_uid', program._uid)
        step = self._step_counters.get(counter_uid, 0)
        self._step_counters[counter_uid] = step + 1
        sp.set_metadata(program=program._uid, step=step)
        from .core import config as _config
        # carried as RAW key data (uint32) so multi-host placement can
        # treat it like any other array; step() re-wraps it. Computed on
        # the HOST cpu backend: the eager key->fold_in->key_data chain on
        # an accelerator is 2-3 tiny dispatches per step that throttle
        # every small-model step. Key derivation is deterministic math,
        # so the stream is identical wherever it is computed.
        with _span('exe/rng'):
            rng = self._host_rng(self._step_seed(program),
                                 _config.rng_impl(), step)

        fetches, new_state = self._dispatch(fn, state, feed_vals, rng)
        out = self._finish(scope, new_state, fetches, return_numpy)
        if checkpoint is not None:
            # the mesh-path equivalent of run_steps' boundary: the scope
            # now holds this step's state, so the policy sees a
            # consistent cut; only the snapshot stalls, the (sharded)
            # write happens on the manager's background thread
            from .core import checkpoint as _ckpt_mod
            st = self._dispatch_stats
            st['dispatches'] += 1
            st['steps'] += 1
            st['ckpt_stall_s'] += checkpoint.step_boundary(
                self, program, scope, self._step_counters[counter_uid])
            st['run_s'] += _time.perf_counter() - t_run
            self._register_profiler_source()
            _ckpt_mod.maybe_drain_preemption(
                checkpoint, self, program, scope,
                self._step_counters[counter_uid])
        return out

    # -- shared run()/run_steps() plumbing -----------------------------
    def _gather_state(self, program, scope):
        """(scope-present persistable state, persistable∩written set,
        out_state_names) — the step function's state contract."""
        persist, persist_written = _program_analysis(program)
        state = {}
        for name in persist:
            val = scope.get(name)
            if val is not None:
                state[name] = val
        out_names = tuple(sorted(set(state) | set(persist_written)))
        return state, set(persist_written), out_names

    def _evict_stale(self, program):
        """Evict compiled steps for older epochs of this program: a
        mutate-then-run loop would otherwise leak one XLA executable per
        mutation. The uid index keeps this O(this program's entries)."""
        keys = self._cache_index.get(program._uid)
        if not keys:
            return
        stale = [k for k in keys if k[1] != program._build_epoch]
        for k in stale:
            keys.discard(k)
            self._cache.pop(k, None)

    @staticmethod
    def _step_seed(program):
        from .core import config as _config
        seed = program.random_seed
        if not seed:
            seed = 1234567 if _config.get_flag('deterministic') \
                else _process_entropy()
        return seed

    def _dispatch(self, fn, state, feed_vals, rng):
        from .core import config as _config
        with _span('exe/dispatch'):
            if _config.get_flag('check_nan_inf'):
                # reference FLAGS_check_nan_inf scans every op output
                # (operator.cc:896-905); jax.debug_nans re-runs the step
                # un-jitted on a nan/inf and pinpoints the producing op
                with jax.debug_nans(True):
                    return fn(state, feed_vals, rng)
            return fn(state, feed_vals, rng)

    @staticmethod
    def _finish(scope, new_state, fetches, return_numpy):
        with _span('exe/finish'):
            for name, val in new_state.items():
                scope.set(name, val)
            if return_numpy:
                return [np.asarray(unwrap(v)) for v in fetches]
            return list(fetches)

    def close(self):
        self._cache.clear()
        self._cache_index.clear()
        self._owned_out.clear()
        self._donation_certs.clear()
        if self._prof_registered:
            from . import profiler as _profiler
            _profiler.unregister_training_source('executor@%x' % id(self))
            _profiler.unregister_infer_source('executor@%x' % id(self))
            self._prof_registered = False

    # ------------------------------------------------------------------
    def run_steps(self, program=None, reader=None, fetch_list=None,
                  steps=None, feed=None, scope=None, return_numpy=True,
                  fetch_policy='final', checkpoint=None):
        """Run K training steps in ONE device dispatch (in-graph loop).

        The traced step body is wrapped in a lax.scan over K pre-staged
        input batches, so optimizer state advances K steps per dispatch
        and the fixed per-dispatch cost divides by K. Bit-identical to K
        sequential
        run() calls: the same per-step rng stream (fold_in over ONE shared
        step counter — run() and run_steps() interleave freely), the same
        state flow, the same op graph per step.

        Feed sources, first match wins:
          * feed= dict name -> stacked [K, ...] array, or a list/tuple of
            K per-step values (LoD values allowed when every step shares
            one bucket shape — data and offsets stack in traced-lod form).
          * reader= a PyReader. In prefetch_to_device(K) mode one staged
            [K, ...] group is popped per call; otherwise `steps` batches
            are pulled and stacked on the spot.
          * neither: the program's attached py_readers (layers.py_reader).

        At EOF a PARTIAL tail group (m < K batches) is flushed through a
        separately compiled m-step program (the multi-bucket discipline of
        inference/export.py); core.EOFException then surfaces on the NEXT
        call, exactly like run().

        fetch_policy: 'final' returns only the LAST step's fetches (the
        every-K thinning a periodic-logging loop wants); 'stack' returns
        every fetch stacked over a leading K axis, bit-matching the K
        sequential per-step fetch values.

        checkpoint: an optional core.checkpoint.CheckpointManager whose
        every-N-steps / every-T-seconds policy is evaluated at this
        dispatch boundary (after the new state is committed to the
        scope). Only the device->host snapshot stalls the loop; the
        write happens on the manager's background thread, and the stall
        is reported as ckpt%% in profiler.training_report().
        """
        with _span('exe/run_steps') as sp:
            return self._run_steps(sp, program, reader, fetch_list, steps,
                                   feed, scope, return_numpy, fetch_policy,
                                   checkpoint)

    def _run_steps(self, sp, program, reader, fetch_list, steps, feed, scope,
                   return_numpy, fetch_policy, checkpoint):
        if fetch_policy not in ('final', 'stack'):
            raise ValueError("fetch_policy must be 'final' or 'stack', "
                             "got %r" % (fetch_policy,))
        if steps is not None and int(steps) < 1:
            raise ValueError("run_steps: steps must be >= 1, got %d"
                             % int(steps))
        program = program if program is not None else default_main_program()
        if hasattr(program, '_ptpu_compiled_program'):
            raise NotImplementedError(
                "run_steps drives single-device programs; the dispatch "
                "floor it amortizes is the per-run() round-trip. Run mesh "
                "(CompiledProgram) programs through Executor.run.")
        scope = scope if scope is not None else global_scope()
        fetch_list = fetch_list or []
        if isinstance(fetch_list, (Variable, str)):
            fetch_list = [fetch_list]
        fetch_names = [_fetch_name(f) for f in fetch_list]

        import time as _time
        t_run = t0 = _time.perf_counter()
        with _span('exe/feed') as feed_sp:
            feed_vals, k, want = self._gather_step_group(program, reader,
                                                         feed, steps)
            feed_sp.set_metadata(n=len(feed_vals))
        stall = _time.perf_counter() - t0

        with _span('exe/prepare'):
            _verify_before_run(program, set(feed_vals), fetch_names)

            state, persist_written, out_state_names = self._gather_state(
                program, scope)
            missing = sorted(persist_written - set(state))
            if missing:
                raise RuntimeError(
                    "run_steps: state %r is written by the program but "
                    "absent from the scope — run the startup program first "
                    "so every state var is materialized (a scan carry "
                    "cannot create entries mid-loop)" % (missing,))

            key = self._cache_key(program, feed_vals, fetch_names, state,
                                  out_state_names) + ('multi', k,
                                                      fetch_policy)
            fn = self._cache.get(key)
        if fn is None:
            with _span('exe/build'):
                self._evict_stale(program)
                fn = self._build_multi(program, tuple(sorted(feed_vals)),
                                       tuple(fetch_names),
                                       out_state_names, k, fetch_policy)
                self._cache[key] = fn
                self._cache_index.setdefault(program._uid, set()).add(key)

        step0 = self._step_counters.get(program._uid, 0)
        self._step_counters[program._uid] = step0 + k
        sp.set_metadata(program=program._uid, step=step0)
        from .core import config as _config
        with _span('exe/rng'):
            rngs = self._host_rng_group(self._step_seed(program),
                                        _config.rng_impl(), step0, k)

        fetches, new_state = self._dispatch(fn, state, feed_vals, rngs)

        st = self._dispatch_stats
        st['dispatches'] += 1
        st['steps'] += k
        if k < want:  # EOF tail group ran through a smaller bucket
            st['tail_flushes'] += 1
        st['host_stall_s'] += stall
        self._register_profiler_source()
        out = self._finish(scope, new_state, fetches, return_numpy)
        if checkpoint is not None:
            # after _finish: the scope now holds this dispatch's state, so
            # a snapshot here is a consistent step-boundary cut
            st['ckpt_stall_s'] += checkpoint.step_boundary(
                self, program, scope, self._step_counters[program._uid])
        st['run_s'] += _time.perf_counter() - t_run
        if checkpoint is not None:
            # graceful preemption (SIGTERM): drain ONE final blocking
            # checkpoint at this boundary — params, step counter, and the
            # data-journal position describing the same history — then
            # exit 0 so the supervisor restarts into a clean resume
            from .core import checkpoint as _ckpt_mod
            _ckpt_mod.maybe_drain_preemption(
                checkpoint, self, program, scope,
                self._step_counters[program._uid])
        return out

    def _register_profiler_source(self):
        if self._prof_registered:
            return
        self._prof_registered = True
        import weakref
        from . import profiler as _profiler
        # weakref: an executor dropped without close() must not pin its
        # stats in the module-global registry forever (and a recycled
        # id() must not resurrect a dead executor's row)
        ref = weakref.ref(self)
        name = 'executor@%x' % id(self)
        infer = self._profile_role == 'infer'
        unreg = (_profiler.unregister_infer_source if infer
                 else _profiler.unregister_training_source)

        def snap():
            ex = ref()
            if ex is None:
                unreg(name)
                raise ReferenceError('executor collected')
            st = ex._dispatch_stats
            d = max(st['dispatches'], 1)
            if infer:  # run_steps driving Predictor.run_batches: the
                # scanned units are inference batches, not train steps
                return {'dispatches': st['dispatches'],
                        'batches': st['steps'],
                        'batches_per_dispatch': st['steps'] / d,
                        'tail_flushes': st['tail_flushes'],
                        'host_stall_ms': st['host_stall_s'] * 1e3}
            return {'dispatches': st['dispatches'], 'steps': st['steps'],
                    'steps_per_dispatch': st['steps'] / d,
                    'tail_flushes': st['tail_flushes'],
                    'host_stall_ms': st['host_stall_s'] * 1e3,
                    # the feeder-saturation headline: share of run_steps
                    # wall time spent WAITING for input (ISSUE 9 drives
                    # this to ~0 with the sharded/pooled data plane)
                    'host_stall_pct': (100.0 * st['host_stall_s']
                                       / st['run_s'])
                    if st['run_s'] else 0.0,
                    'ckpt_stall_ms': st['ckpt_stall_s'] * 1e3,
                    'ckpt_stall_pct': (100.0 * st['ckpt_stall_s']
                                       / st['run_s'])
                    if st['run_s'] else 0.0}
        (_profiler.register_infer_source if infer
         else _profiler.register_training_source)(name, snap)

    def _gather_step_group(self, program, reader, feed, steps):
        """Resolve one K-step input group to ({name: stacked device
        value} with leading dim K, realized K, intended K) — realized <
        intended only at an EOF tail flush (the intended size comes from
        `steps` or the reader's configured group)."""
        from .core import EOFException
        if feed:
            groups, ks = {}, set()
            for name, value in feed.items():
                var = self._feed_var(program, name)
                if isinstance(value, (list, tuple)):
                    groups[name] = self._stack_step_values(
                        name, list(value), var)
                    ks.add(len(value))
                    continue
                v = self._to_device_value(value, var)
                if isinstance(v, LoDArray):
                    raise TypeError(
                        "run_steps feed %r: pass LoD values as a list of K "
                        "per-step LoDTensors (one stacked array cannot "
                        "carry per-step offsets)" % name)
                if getattr(v, 'ndim', 0) < 1:
                    raise ValueError(
                        "run_steps feed %r has no leading step dimension"
                        % name)
                groups[name] = v
                ks.add(int(v.shape[0]))
            if len(ks) != 1:
                raise ValueError(
                    "run_steps: feeds disagree on the step dimension: %s"
                    % sorted(ks))
            k = ks.pop()
            if steps is not None and int(steps) != k:
                raise ValueError(
                    "run_steps(steps=%d) but the feed carries %d stacked "
                    "steps" % (int(steps), k))
            return groups, k, k

        readers = [reader] if reader is not None else \
            list(getattr(program, '_py_readers', []))
        if not readers:
            raise ValueError(
                "run_steps needs a feed source: pass feed= (stacked "
                "arrays or K-lists), reader=, or attach a py_reader to "
                "the program")
        groups, ks, wants = {}, set(), set()
        for r in readers:
            # the mode the reader's last start() ran with; before any
            # start() fall back to the configured mode so the steps
            # validation and not-started errors surface on the right path
            pre_k = getattr(r, '_mode_k', 0)
            if not pre_k and getattr(r, '_thread', None) is None:
                pre_k = getattr(r, '_prefetch_k', None) or 0
            if pre_k:
                if steps is not None and int(steps) != pre_k:
                    raise ValueError(
                        "run_steps(steps=%d) but the reader prefetches "
                        "groups of %d — configure prefetch_to_device "
                        "with the dispatch size" % (int(steps), pre_k))
                batch, k = r._next_group()  # EOFException when drained
                for n, v in batch.items():
                    groups[n] = self._to_device_value(
                        v, self._feed_var(program, n))
                ks.add(k)
                wants.add(pre_k)
                continue
            if steps is None:
                raise ValueError(
                    "run_steps(steps=K) is required when the reader does "
                    "not prefetch fixed-size groups")
            if getattr(r, '_pending_eof', False):
                r._pending_eof = False
                raise EOFException("py_reader reached end of data")
            pulled = []
            try:
                for _ in range(int(steps)):
                    pulled.append(r._next_batch())
            except EOFException:
                if not pulled:
                    raise
                r._pending_eof = True  # tail flush now, EOF on next call
            for n in pulled[0]:
                groups[n] = self._stack_step_values(
                    n, [b[n] for b in pulled], self._feed_var(program, n))
            ks.add(len(pulled))
            wants.add(int(steps))
        if len(ks) != 1:
            raise ValueError("run_steps: attached readers disagree on the "
                             "group size: %s" % sorted(ks))
        return groups, ks.pop(), max(wants)

    def _stack_step_values(self, name, values, var):
        """Stack K per-step feed values into one [K, ...] device value.

        LoD values follow the executor's static/traced duality: when every
        step carries the IDENTICAL static lod pattern, the group stacks in
        STATIC form (offsets stay host structure, so ops whose output
        shape depends on lod content — CTC, sequence_expand — keep
        working); otherwise the group stacks in TRACED form (data + one
        offsets array per level), which requires every step to share one
        bucket shape — the bucket_by_length discipline — and traced-lod
        capable ops."""
        vals = [self._to_device_value(v, var) for v in values]
        if isinstance(vals[0], LoDArray):
            nlv = vals[0].nlevels
            shapes = {tuple(v.data.shape) for v in vals
                      if isinstance(v, LoDArray)}
            if (any(not isinstance(v, LoDArray) or v.nlevels != nlv
                    for v in vals) or len(shapes) != 1):
                raise ValueError(
                    "run_steps feed %r: every step in a group must share "
                    "one LoD bucket shape (pad/bucket the reader, e.g. "
                    "bucket_by_length); got data shapes %s"
                    % (name, sorted(shapes)))
            if (all(not v.is_traced for v in vals)
                    and len({v.lod for v in vals}) == 1):
                # identical static pattern across the group: the scan
                # slices data while the offsets ride the pytree STRUCTURE
                return LoDArray(jnp.stack([v.data for v in vals]),
                                vals[0].lod)
            offs = []
            for lvl in range(nlv):
                level = [v.off_t(lvl) for v in vals]
                if len({int(o.shape[0]) for o in level}) != 1:
                    raise ValueError(
                        "run_steps feed %r lod level %d: offset counts "
                        "differ across the group (nseq must match the "
                        "bucket)" % (name, lvl))
                offs.append(jnp.stack(level))
            return LoDArray.traced(jnp.stack([v.data for v in vals]), offs)
        if any(isinstance(v, LoDArray) for v in vals):
            raise ValueError("run_steps feed %r mixes LoD and dense "
                             "values across the group" % name)
        return jnp.stack(vals)

    def _build_multi(self, program, feed_names, fetch_names,
                     out_state_names, k, fetch_policy):
        """Compile a K-step dispatch: the single-step trace body wrapped
        in a lax.scan over stacked feeds + per-step rng keys. One cache
        entry per (signature, K) — an EOF tail group of m < K steps
        compiles its own smaller bucket, the multi-bucket discipline of
        inference/export.py. Gradient merge composes: each scanned step
        runs the existing micro-batch scan inside it."""
        step = self._trace_step_fn(program, fetch_names, out_state_names,
                                   None)

        def step_k(state, feed, rngs):
            def one(st, feed_i, rng_i):
                fetches, new_state = step(st, feed_i, rng_i)
                st = dict(st)
                st.update(new_state)
                return st, fetches

            # 'final' thinning carries the fetches through the scan (no
            # K-stacked fetch buffer); seed the carry with zeros of the
            # fetch avals
            feed0 = jax.tree.map(lambda x: x[0], feed)
            f_sh = jax.eval_shape(lambda s, f, r: one(s, f, r)[1],
                                  state, feed0, rngs[0])
            zero_f = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  f_sh)

            def body(carry, xs):
                st, _ = carry
                feed_i, rng_i = xs
                st, fetches = one(st, feed_i, rng_i)
                ys = fetches if fetch_policy == 'stack' else None
                return (st, fetches), ys

            (st, last_f), ys = jax.lax.scan(body, (state, zero_f),
                                            (feed, rngs))
            fetches = ys if fetch_policy == 'stack' else last_f
            new_state = {n: st[n] for n in out_state_names if n in st}
            return fetches, new_state
        step_k.__name__ = step_k.__qualname__ = _step_name(program,
                                                           multi=True)

        return self._pin_and_call(
            jax.jit(step_k, donate_argnums=(0,)),
            key_parts=self._aot_key_parts(program, fetch_names,
                                          out_state_names,
                                          extra=('multi', k, fetch_policy)),
            tag=self._cache_tag('executor_steps', program), fun=step_k,
            donate_state=self._donation_safe(program, feed_names,
                                             fetch_names,
                                             out_state_names))

    def _aot_key_parts(self, program, fetch_names, out_state_names,
                       extra=()):
        """Trace-time inputs the persistent compile cache must key on but
        cannot see in the arg avals (core/compile_cache.py); None when the
        cache is off so the program-desc walk costs nothing."""
        from .core import compile_cache as _cc
        if not _cc.enabled():
            return None
        from .core import config as _config
        return ('step', _cc.program_fingerprint(program),
                tuple(fetch_names), tuple(out_state_names),
                bool(getattr(program, '_amp_bf16', False)),
                int(getattr(program, '_grad_accum_k', 1) or 1),
                _config.rng_impl(),
                int(_config.get_flag('dropout_bits') or 0)) + tuple(extra)

    def _cache_tag(self, base, program):
        """Compile-cache entry tag: '-int8' suffix for quantized programs
        so `cache_ctl stats` shows the quantized tier per tag."""
        from .core import compile_cache as _cc
        return _cc.quant_tag(base, program)

    def _donation_safe(self, program, feed_names, fetch_names,
                       out_state_names):
        """True when the dataflow certifier proves the state dict may be
        donated on a RELOADED executable (passes/dataflow.py): the
        round-8 warm-path copy tax is paid only when safety is
        unprovable. PTPU_WARM_DONATION=0 opts out wholesale. The
        certificate is kept on the executor (last per program uid) for
        tests and the doctor to inspect."""
        import os as _os
        from .passes import dataflow as _dataflow
        if _os.environ.get('PTPU_WARM_DONATION', '1') in (
                '0', 'false', 'off'):
            cert = _dataflow.DonationCertificate(
                False, (), ['disabled by PTPU_WARM_DONATION=0'], 0,
                out_state_names)
        else:
            cert = _dataflow.certify_donation(
                program, out_state_names, feed_names=feed_names,
                fetch_names=fetch_names)
        self._donation_certs[program._uid] = cert
        return cert.safe

    def _resolve_aot(self, jitted, fun, args, key_parts, tag,
                     donate_state=False):
        """Persistent-cache warm start for a (state, feed, rng) callable,
        resolved on the FIRST call (AOT needs concrete avals): a tier-1
        hit deserializes the executable (zero trace, zero compile); a miss
        compiles once and persists. Falls back to plain `jitted` when the
        cache is off or debug_nans needs the re-traceable path. `fun` is
        the raw step callable the cache compiles from; state donation is
        applied only under a dataflow donation certificate
        (`donate_state`, compile_cache.aot_or_jit's reload-aliasing
        contract)."""
        from .core import compile_cache as _cc
        from .core import config as _config
        if key_parts is None or not _cc.enabled() \
                or _config.get_flag('check_nan_inf'):
            return jitted
        return _cc.aot_or_jit(jitted, args, key_parts, tag=tag, fun=fun,
                              device=self._device,
                              donate_argnums=(0,) if donate_state
                              else None)

    def _pin_and_call(self, jitted, key_parts=None, tag='executor',
                      fun=None, donate_state=False):
        """Wrap a jitted (state, feed, rng) callable so every input is
        pinned to this executor's device, COMMITTED — keeps
        avals/shardings identical across runs (no silent pjit recompiles)
        and gathers state left sharded across a mesh by an earlier
        ParallelExecutor run on the same scope. Shared by the single-step
        and multi-step build paths. With the persistent compile cache on,
        the first call resolves through it (AOT warm start)."""
        dev = self._device
        fn_box = [None]

        def _pin(v):
            # skip arrays already committed here
            data = v.data if isinstance(v, LoDArray) else v
            s = getattr(data, 'sharding', None)
            if s is not None and s.device_set == {dev}:
                return v
            return jax.device_put(v, dev)

        def _own_leaf(x):
            # donated-state leaves must live in XLA-OWNED buffers. A
            # RELOADED donating executable honors its baked-in aliasing
            # WITHOUT jax's external-buffer guard, and zero-copy views
            # of host memory reach the scope from several doors —
            # device_put of numpy on cpu backends, jnp.asarray over a
            # checkpoint/model payload (io._deserialize_tensor), user
            # arrays — so it would scribble over / free memory it does
            # not own (measured: NaN then heap corruption on the
            # kill-resume path). The only leaves provably XLA-owned are
            # the ones OUR donated dispatches produced (_owned_out);
            # everything else gets one owned copy at this boundary.
            # Steady state (outputs feeding the next dispatch) passes
            # through untouched: the per-step copy stays eliminated.
            if isinstance(x, jax.Array) and id(x) in self._owned_out:
                return x
            with jax.default_device(dev):
                return jnp.array(x, copy=True)

        def _note_owned(tree):
            owned = self._owned_out
            leaves = [l for l in jax.tree.leaves(tree)
                      if isinstance(l, jax.Array)]
            cap = max(1024, 4 * len(leaves))
            if len(owned) > cap:
                # with donation in effect old generations are deleted
                # shells (free); when a fallback executable is silently
                # undonated they stay LIVE — prune the dead, then bound
                # the live set to a few generations so the registry can
                # never pin unbounded state memory
                for k in [k for k, v in owned.items() if v.is_deleted()]:
                    del owned[k]
                while len(owned) > cap:
                    owned.pop(next(iter(owned)))
            for l in leaves:
                owned[id(l)] = l

        def call(state, feed, rng):
            if donate_state:
                state = {n: jax.tree.map(_own_leaf, v)
                         for n, v in state.items()}
            state = {n: _pin(v) for n, v in state.items()}
            feed = {n: _pin(v) for n, v in feed.items()}
            rng = _pin(rng)
            fn = fn_box[0]
            if fn is None:
                fn = self._resolve_aot(jitted, fun, (state, feed, rng),
                                       key_parts, tag,
                                       donate_state=donate_state)
                fn_box[0] = fn
            try:
                with jax.default_device(dev):
                    out = fn(state, feed, rng)
            finally:
                if donate_state:
                    # the dispatch CONSUMED these buffers (scribbled in
                    # place on success, possibly torn on failure):
                    # evict them so a stale object re-submitted later
                    # is copied — or raises on a deleted array — never
                    # passed through into a reloaded aliasing
                    # executable
                    for v in state.values():
                        for l in jax.tree.leaves(v):
                            self._owned_out.pop(id(l), None)
            if donate_state:
                _note_owned(out[1])   # new_state: next dispatch's input
            return out
        return call

    # ------------------------------------------------------------------
    @staticmethod
    def _host_rng(seed, impl, step):
        """Per-step raw key data, derived on the host cpu backend (numpy
        result). Cached base key per (seed, impl)."""
        cpu = Executor._host_cpu()
        if cpu is None and impl == 'threefry2x32':
            # no cpu backend registered (JAX_PLATFORMS=tpu, ADVICE r5
            # item 3): numpy-side derivation, bit-identical to jax's
            return _np_threefry_key_group(seed, step, 1)[0]
        base = Executor._base_key(seed, impl, cpu)
        with (jax.default_device(cpu) if cpu is not None
              else _nullcontext()):
            return np.asarray(jax.random.key_data(
                jax.random.fold_in(base, step)))

    @staticmethod
    def _host_rng_group(seed, impl, step0, k):
        """Raw key data for steps [step0, step0+k), stacked [k, ...]: ONE
        host-side derivation feeds a whole multi-step dispatch, and each
        row is bit-identical to _host_rng(seed, impl, step0 + i) — the
        K-step program consumes the same rng stream K sequential run()
        calls would."""
        cpu = Executor._host_cpu()
        if cpu is None and impl == 'threefry2x32':
            return _np_threefry_key_group(seed, step0, k)
        base = Executor._base_key(seed, impl, cpu)
        with (jax.default_device(cpu) if cpu is not None
              else _nullcontext()):
            steps = jnp.arange(step0, step0 + k, dtype=jnp.int32)
            return np.asarray(_fold_keys(base, steps))

    @staticmethod
    def _host_cpu():
        """The host cpu device, or None when the cpu platform is not
        registered (JAX_PLATFORMS=tpu) — callers fall back to numpy-side
        key math (threefry) or the default device (rbg et al.; key
        derivation is deterministic math, so the stream is identical
        wherever it is computed)."""
        try:
            return jax.local_devices(backend='cpu')[0]
        except RuntimeError:
            return None

    @staticmethod
    def _base_key(seed, impl, cpu):
        cache = Executor._host_rng_cache
        base = cache.get((seed, impl, cpu is None))
        if base is None:
            with (jax.default_device(cpu) if cpu is not None
                  else _nullcontext()):
                base = jax.random.key(seed, impl=impl)
            cache[(seed, impl, cpu is None)] = base
        return base

    _host_rng_cache = {}

    # ------------------------------------------------------------------
    def _feed_var(self, program, name):
        for b in program.blocks:
            if name in b.vars:
                return b.vars[name]
        return None

    def _to_device_value(self, value, var=None):
        if isinstance(value, LoDArray):
            return value
        dtype = var.dtype if var is not None and var.dtype else None
        if isinstance(value, jax.Array):
            # already on device: never round-trip through the host
            if dtype:
                want = jax.dtypes.canonicalize_dtype(np.dtype(dtype))
                if value.dtype != want:
                    value = value.astype(want)
            return value
        # host-side LoDTensor from lod_tensor.py
        lod = getattr(value, 'lod', None)
        data = getattr(value, 'data', value)
        if callable(lod):  # reference-style LoDTensor API
            lod, data = value.lod(), np.asarray(value)
        with jax.default_device(self._device):
            # runtime_dtype canonicalizes declared int64/float64 to the
            # 32-bit carrier up front instead of warning per feed
            arr = jnp.asarray(np.asarray(data),
                              dtype=framework.runtime_dtype(dtype))
        arr = jax.device_put(arr, self._device)
        if lod:
            return LoDArray(arr, [np.asarray(l, np.int32) for l in lod])
        return arr

    def _sig(self, v):
        if isinstance(v, LoDArray):
            if v.is_traced:
                # traced lod: offsets are data — the compiled program is
                # lod-generic, so only bucket SHAPES key the cache
                return ('lodt', v.data.shape, str(v.data.dtype),
                        tuple(int(o.shape[0]) for o in v._lod_t))
            # static lod offsets are structure: part of the compile key
            return ('lod', v.data.shape, str(v.data.dtype), v.lod)
        return (tuple(np.shape(v)), str(getattr(v, 'dtype', type(v).__name__)))

    def _cache_key(self, program, feed_vals, fetch_names, state, out_names):
        from .core import config as _config
        return (program._uid, program._build_epoch,
                tuple((n, self._sig(v)) for n, v in sorted(feed_vals.items())),
                tuple(fetch_names),
                tuple((n, self._sig(v)) for n, v in sorted(state.items())),
                out_names, bool(getattr(program, '_amp_bf16', False)),
                int(getattr(program, '_grad_accum_k', 1) or 1),
                # trace-time flags that change the compiled numerics:
                # toggling them must recompile, not silently reuse
                _config.rng_impl(),
                int(_config.get_flag('dropout_bits') or 0))

    @staticmethod
    def _ga_partition(program, fetch_names):
        """Split the block for gradient merge (ref multi_batch_merge_pass).

        The scan cone — repeated per microbatch inside lax.scan — is the
        ancestor set of the RAW gradients. Optimize-role ops and tagged
        grad-transform ops (gradient clip / weight decay, clip.py /
        regularizer.py `_grad_transform`) are excluded from the cone, so
        clipping/decay applies ONCE to the merged gradient, matching the
        reference pass (accumulate raw grads, transform once). Outer ops
        are pruned to those reachable from fetches/persistables (a metric
        op nobody fetches must not drag scan intermediates out)."""
        from .backward import OP_ROLE_OPTIMIZE, OP_ROLE_BACKWARD
        ops = list(program.global_block().ops)
        excl = {i for i, op in enumerate(ops)
                if int(op.attrs.get('op_role', 0)) == OP_ROLE_OPTIMIZE
                or op.attrs.get('_grad_transform')}
        # the cone's roots are the RAW GRADIENTS: excluded-op inputs that a
        # backward-role non-excluded op produces. Params/moments (state) and
        # the LR schedule (forward-role) must NOT seed the cone — pulling
        # the LR counter chain into the scan would tick it k times per step
        bwd_out = {o for i, op in enumerate(ops) if i not in excl
                   and int(op.attrs.get('op_role', 0)) & OP_ROLE_BACKWARD
                   for o in op.output_arg_names() if o}
        seed = {n for i in excl for n in ops[i].input_arg_names()
                if n in bwd_out}
        needed = set(seed)
        scan_set = set()
        for i in range(len(ops) - 1, -1, -1):
            if i in excl or ops[i].type == 'feed':
                continue
            if any(o in needed for o in ops[i].output_arg_names()):
                scan_set.add(i)
                needed |= {n for n in ops[i].input_arg_names() if n}
        scan_idx = sorted(scan_set)
        scan_outs = {n for i in scan_idx
                     for n in ops[i].output_arg_names() if n}
        persist = {v.name for v in program.list_vars() if v.persistable}
        # prune outer ops: keep excluded (clip/decay/optimize) ops plus any
        # op reachable backward from fetches / persistable writes
        keep_out = set(fetch_names) | persist
        outer_set = set()
        for i in range(len(ops) - 1, -1, -1):
            if i in scan_set or ops[i].type == 'feed':
                continue
            if i in excl or any(o in keep_out
                                for o in ops[i].output_arg_names()):
                outer_set.add(i)
                keep_out |= {n for n in ops[i].input_arg_names() if n}
        outer_idx = sorted(outer_set)
        # everything the outer phase consumes from the scan is accumulated
        outer_reads = {n for i in outer_idx
                       for n in ops[i].input_arg_names() if n}
        carried = sorted((outer_reads | set(fetch_names)) & scan_outs)
        return ops, scan_idx, outer_idx, carried, scan_outs

    def _ga_step(self, program, state, feed, rng, k, ops, scan_idx,
                 outer_idx, carried, persist_scan, fetch_names,
                 out_state_names):
        """Gradient merge (ref framework/ir/multi_batch_merge_pass.cc, SURVEY
        maps it to lax.scan microbatching): slice the fed batch into k
        microbatches, scan the raw-gradient cone accumulating (1/k)-scaled
        values (so the merged grad equals the one big batch's mean-loss
        grad), then run the outer ops — gradient clip/decay, LR schedule,
        optimizer — once on the merged values."""
        block = program.global_block()
        for n, v in feed.items():
            if isinstance(v, LoDArray):
                raise TypeError("gradient merge does not support LoD feeds "
                                "(pad/bucket first): %r" % n)
            if v.shape[0] % k:
                raise ValueError(
                    "gradient merge: batch %d of feed %r is not divisible "
                    "by num_microbatches=%d" % (v.shape[0], n, k))
        sliced = {n: v.reshape((k, v.shape[0] // k) + v.shape[1:])
                  for n, v in feed.items()}
        pers0 = {n: state[n] for n in persist_scan if n in state}
        outer_reads = {n for i in outer_idx
                       for n in ops[i].input_arg_names() if n}

        def micro(mb_feed, mb_rng, pers):
            tracer = Tracer(program, mb_rng)
            tracer.env.update(state)
            tracer.env.update(pers)
            tracer.env.update(mb_feed)
            for i in scan_idx:
                tracer.run_op(ops[i], block)
            env = tracer.env
            acc = {n: env[n] for n in carried}
            new_pers = {n: env[n] for n in pers}
            return acc, new_pers

        mb0 = {n: v[0] for n, v in sliced.items()}
        a_sh, _ = jax.eval_shape(micro, mb0, rng, pers0)
        for n, s in a_sh.items():
            if not jnp.issubdtype(s.dtype, jnp.floating):
                raise TraceError(
                    "gradient merge cannot carry %r (dtype %s) out of the "
                    "microbatch scan: only float values average across "
                    "microbatches. Fetch the loss or a persistable instead."
                    % (n, s.dtype))
            if n in fetch_names and n not in outer_reads \
                    and int(np.prod(s.shape)) != 1:
                raise TraceError(
                    "fetch %r has per-microbatch shape %s under gradient "
                    "merge; only scalar (loss-like) fetches are "
                    "well-defined — per-example outputs of a microbatch "
                    "scan would silently average. Fetch the loss, or run "
                    "without gradient merge." % (n, tuple(s.shape)))
        zeros = {n: jnp.zeros(s.shape, s.dtype) for n, s in a_sh.items()}

        def body(carry, xs):
            acc, pers = carry
            mb, i = xs
            a, pers = micro(mb, jax.random.fold_in(rng, i), pers)
            acc = jax.tree.map(lambda x, y: x + y / k, acc, a)
            return (acc, pers), None

        (acc, pers), _ = jax.lax.scan(body, (zeros, pers0),
                                      (sliced, jnp.arange(k)))

        tracer = Tracer(program, rng)
        tracer.env.update(state)
        tracer.env.update(acc)
        tracer.env.update(pers)
        for i in outer_idx:
            tracer.run_op(ops[i], block)
        env = tracer.env
        missing = [n for n in fetch_names if n not in env]
        if missing:
            raise TraceError(
                "fetch %r is computed inside the gradient-merge microbatch "
                "scan and is not a carried output; fetch the loss or a "
                "persistable instead" % (missing,))
        fetches = [env[n] for n in fetch_names]
        new_state = {n: env[n] for n in out_state_names if n in env}
        return fetches, new_state

    def _trace_step_fn(self, program, fetch_names, out_state_names, mesh):
        """The traced (state, feed, rng_raw) -> (fetches, new_state) step
        body — shared by the single-step _build and the K-step
        _build_multi (which wraps it in a lax.scan)."""
        amp_on = bool(getattr(program, '_amp_bf16', False))
        k = int(getattr(program, '_grad_accum_k', 1) or 1)

        if k > 1:
            (ga_ops, ga_scan, ga_outer, ga_carried,
             ga_scan_outs) = self._ga_partition(program, fetch_names)
            persist_all = set(_program_analysis(program)[0])
            ga_persist = sorted(persist_all & ga_scan_outs)
            ga_carried = [n for n in ga_carried if n not in ga_persist]

        from .core import config as _config
        rng_impl = _config.rng_impl()

        from .parallel.mesh import trace_mesh_scope

        def step(state, feed, rng_raw):
            rng = jax.random.wrap_key_data(rng_raw, impl=rng_impl)
            # amp/mesh scopes are trace-time flags: the body below runs
            # exactly once per compile, so the contexts govern which
            # lowering the ops pick (core/amp.py bf16 routes; ring
            # attention over the compile mesh), not per-step state
            with amp.scope(amp_on), trace_mesh_scope(mesh):
                if k > 1:
                    return self._ga_step(program, state, feed, rng, k,
                                         ga_ops, ga_scan, ga_outer,
                                         ga_carried, ga_persist, fetch_names,
                                         out_state_names)
                tracer = Tracer(program, rng)
                tracer.env.update(state)
                tracer.env.update(feed)
                tracer.run_block(program.global_block())
                fetches = [tracer.env[n] for n in fetch_names]
                new_state = {n: tracer.env[n] for n in out_state_names
                             if n in tracer.env}
            return fetches, new_state
        return step

    def _build(self, program, feed_names, fetch_names, state_names,
               out_state_names, mesh=None, feed_vals=None):
        step = self._trace_step_fn(program, fetch_names, out_state_names,
                                   mesh)
        step.__name__ = step.__qualname__ = _step_name(program)

        if mesh is None:
            return self._pin_and_call(
                jax.jit(step, donate_argnums=(0,)),
                key_parts=self._aot_key_parts(program, fetch_names,
                                              out_state_names),
                tag=self._cache_tag('executor_run', program), fun=step,
                donate_state=self._donation_safe(program, feed_names,
                                                 fetch_names,
                                                 out_state_names))

        # SPMD: batch-shard the feeds over the data axis; state replicated
        # unless a parameter carries a sharding_spec (TP/EP annotation);
        # GSPMD partitions the program and inserts gradient all-reduces
        # (subsumes ParallelExecutor + nccl2 + pserver-dense, SURVEY §2.4).
        # The annotation + optimizer-slot-inheritance rule lives in
        # parallel/reshard.py — ONE copy shared with the pod checkpoint
        # manager's topology-change restore, so restore-time resharding
        # and dispatch-time placement can never disagree.
        from .parallel.mesh import replicated, batch_sharded, DATA_AXIS
        from .parallel.reshard import state_shardings_for
        rep = replicated(mesh)
        ndp = mesh.shape.get(DATA_AXIS, 1)
        state_shardings, _specs = state_shardings_for(program, mesh,
                                                      state_names)

        from .parallel import multihost
        multi = multihost.mesh_spans_processes(mesh)
        nproc = len({d.process_index
                     for d in np.asarray(mesh.devices).reshape(-1)})

        def feed_spec(name):
            v = feed_vals.get(name)
            arr = unwrap(v) if v is not None else None
            # each process feeds its LOCAL shard: the global batch dim is
            # local_rows x nproc when the mesh spans hosts
            rows = (arr.shape[0] * (nproc if multi else 1)
                    if arr is not None and getattr(arr, 'ndim', 0) >= 1
                    else 0)
            if rows > 0 and rows % ndp == 0:
                if isinstance(v, LoDArray):
                    return None  # lod arrays: replicate (offsets are global)
                return batch_sharded(mesh, arr.ndim)
            return rep

        feed_specs = {n: feed_spec(n) or rep for n in feed_names}

        # pin the state FIXED POINT: without an output constraint GSPMD
        # picks new_state shardings freely (e.g. shards an unannotated
        # param it decided to split), so step 2's inputs no longer match
        # the shardings step 1 compiled for — a recompile per step under
        # plain jit, a hard mismatch error through the AOT warm path.
        # Constraining every state output to its input sharding makes the
        # step function a sharding-stable loop with ONE signature.
        base_step = step

        def step(state, feed, rng):
            fetches, new_state = base_step(state, feed, rng)
            new_state = {
                n: jax.lax.with_sharding_constraint(
                    v, state_shardings.get(n, rep))
                for n, v in new_state.items()}
            return fetches, new_state
        step.__name__ = step.__qualname__ = base_step.__name__
        jitted = jax.jit(step, donate_argnums=(0,))

        def _place_feed(n, v):
            spec = feed_specs[n]
            if multi and spec is not rep and not isinstance(v, LoDArray):
                # each trainer holds its LOCAL batch shard; assemble the
                # global batch-sharded array (test_dist_base semantics —
                # every process feeds its own slice)
                return multihost.place_local_shard(spec, np.asarray(v),
                                                   nproc)
            return _mesh_put(v, spec)

        def _mesh_put_leaf(v, sharding):
            if isinstance(v, jax.Array) and not v.is_fully_addressable:
                return v  # already global (previous step's output)
            host = np.asarray(v)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])

        def _mesh_put(v, sharding):
            # device_put cannot target non-addressable shardings: under
            # multi-host, build the global array from each process's
            # (identical) host copy instead. tree_map handles LoDArray and
            # other pytree values leaf-wise.
            if multi:
                return jax.tree.map(lambda x: _mesh_put_leaf(x, sharding), v)
            return jax.device_put(v, sharding)

        aot_parts = self._aot_key_parts(program, fetch_names,
                                        out_state_names, extra=('mesh',))
        fn_box = [None]

        def run_with_mesh(state, feed, rng):
            # place inputs on the mesh (resharding no-op when already there);
            # jit compiles to the arg shardings, GSPMD does the rest
            with _span('exe/place', n=len(state) + len(feed) + 1):
                state = {n: _mesh_put(v, state_shardings.get(n, rep))
                         for n, v in state.items()}
                feed = {n: _place_feed(n, v) for n, v in feed.items()}
                rng = _mesh_put(rng, rep)
            fn = fn_box[0]
            if fn is None:
                from .core import compile_cache as _cc
                from .core import config as _config
                fn = jitted
                if aot_parts is not None and _cc.enabled() \
                        and not _config.get_flag('check_nan_inf'):
                    with mesh:
                        fn = _cc.aot_or_jit(jitted, (state, feed, rng),
                                            aot_parts, tag='executor_mesh',
                                            fun=step, mesh=mesh)
                fn_box[0] = fn
            with mesh:
                return fn(state, feed, rng)
        return run_with_mesh


# ---------------------------------------------------------------------------
# compiled-step memory accounting (ISSUE 18 measurement layer)
# ---------------------------------------------------------------------------
def compiled_memory_stats(program=None, feed=None, fetch_list=None,
                          scope=None, exe=None):
    """Compile (but do not run) the single-step function for
    (program, feed, fetch_list) and return the XLA buffer-assignment
    numbers from ``Compiled.memory_analysis()``:

        {'temp_bytes', 'argument_bytes', 'output_bytes', 'alias_bytes',
         'generated_code_bytes', 'peak_bytes'}

    temp_bytes is the activation working set the buffer assigner plans —
    the number activation rematerialization shrinks; peak_bytes =
    arguments + outputs + temps - aliased (donated state re-used in
    place). Available on the CPU proxy backend, so CI can gate it.
    Returns None when the backend exposes no memory analysis. The
    compile lands in XLA's compilation cache, so a subsequent run() of
    the same boundary does not pay it twice.
    """
    program = program if program is not None else default_main_program()
    exe = exe if exe is not None else Executor()
    scope = scope if scope is not None else global_scope()
    fetch_list = fetch_list or []
    if isinstance(fetch_list, (Variable, str)):
        fetch_list = [fetch_list]
    fetch_names = tuple(_fetch_name(f) for f in fetch_list)
    feed = feed or {}
    feed_vals = {n: exe._to_device_value(v, exe._feed_var(program, n))
                 for n, v in feed.items()}
    state, _, out_state_names = exe._gather_state(program, scope)
    step = exe._trace_step_fn(program, fetch_names, out_state_names, None)
    from .core import config as _config
    rng = exe._host_rng(exe._step_seed(program), _config.rng_impl(), 0)

    # lower from avals, not values: scope state may live sharded over a
    # mesh (a ParallelExecutor ran on this scope) while feeds sit on one
    # device, and concrete args would make jit reject the device mix
    def _avals(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                getattr(x, 'shape', None) if getattr(x, 'shape', None)
                is not None else np.shape(x),
                getattr(x, 'dtype', None) or np.asarray(x).dtype), tree)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        _avals(state), _avals(feed_vals), _avals(rng)).compile()
    ma = compiled.memory_analysis()
    if ma is None:
        return None

    def _grab(*names):
        for n in names:
            v = getattr(ma, n, None)
            if v is not None:
                return int(v)
        return 0

    out = {
        'temp_bytes': _grab('temp_size_in_bytes'),
        'argument_bytes': _grab('argument_size_in_bytes'),
        'output_bytes': _grab('output_size_in_bytes'),
        'alias_bytes': _grab('alias_size_in_bytes'),
        'generated_code_bytes': _grab('generated_code_size_in_bytes'),
    }
    out['peak_bytes'] = (out['argument_bytes'] + out['output_bytes']
                         + out['temp_bytes'] - out['alias_bytes'])
    return out
