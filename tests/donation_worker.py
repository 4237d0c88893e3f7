"""Subprocess worker for test_dataflow.py and donation_smoke.py: one
fresh-process train run through the persistent compile cache, reporting
whether certified state donation was active and whether it actually
eliminated the per-step state copy.

    python donation_worker.py CACHE_DIR OUT.npz

Env: PTPU_COMPILE_CACHE=0 turns the cache off (the uncached reference);
PTPU_WARM_DONATION=0 keeps the cache but forces the undonated round-8
behavior (the copy-tax control arm); PTPU_DONATION_WORKER_RESEED=1
round-trips the scope state through HOST numpy between the two groups —
the restored-checkpoint shape of the zero-copy hazard (a reloaded
donating executable must never scribble over host-backed buffers; the
executor copies such leaves to XLA-owned memory at the boundary), so
the fetches must stay byte-identical to the un-reseeded run.

Runs startup + two K=3 run_steps groups on a deterministic fc net,
saves every fetch and the final persistable state to OUT.npz, and
prints one DONATION_STATS JSON line:

  cert_safe       the dataflow certifier's verdict for this program
  exec_hits/misses/xla_compiles_net   compile-cache counters
  donated_entries how many on-disk entries record donated=True
  old_deleted     state buffers jax marked deleted after dispatch 2
                  (donation executed — the copy is gone)
  aliased_state   new state buffers that landed on the OLD buffer's
                  address (XLA aliased the update in place)
  state_total     donated state var count
"""
import json
import os
import sys


def main():
    cache_dir, out_path = sys.argv[1], sys.argv[2]
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ.setdefault('PTPU_COMPILE_CACHE', '1')
    os.environ['JAX_COMPILATION_CACHE_DIR'] = cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)

    import glob
    import time
    import warnings

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from paddle_tpu.core import compile_cache as cc

    t0 = time.perf_counter()

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 11
    with fluid.program_guard(main_p, startup), unique_name.guard():
        x = fluid.layers.data(name='x', shape=[6], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(x, size=8, act='relu')
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(fluid.layers.square(pred - y))
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)

    rng = np.random.RandomState(0)
    groups = [{'x': rng.randn(3, 4, 6).astype(np.float32),
               'y': rng.randn(3, 4, 1).astype(np.float32)}
              for _ in range(2)]

    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    save = {}
    with fluid.scope_guard(scope), warnings.catch_warnings():
        # XLA backends without usable donation warn per call; the probe
        # below MEASURES donation instead of trusting the absence of the
        # warning, so keep the output parseable
        warnings.filterwarnings(
            'ignore', message='Some donated buffers were not usable')
        exe.run(startup)
        out, = exe.run_steps(main_p, feed=groups[0], fetch_list=[loss],
                             fetch_policy='stack')
        save['g0'] = np.asarray(out)

        if os.environ.get('PTPU_DONATION_WORKER_RESEED') == '1':
            # the restore shape of the zero-copy hazard: state re-enters
            # the scope as host numpy; the next (possibly reloaded,
            # donating) dispatch must copy it to owned buffers, never
            # donate it in place
            for n, v in list(scope._vars.items()):
                if v is not None:
                    scope.set(n, np.array(np.asarray(v), copy=True))

        # probe dispatch 2: donation shows as the old buffers dying (and
        # usually the new state landing at the same addresses)
        import jax
        old = {}
        for n, v in scope._vars.items():
            if isinstance(v, jax.Array) and not v.is_deleted():
                try:
                    old[n] = (v, v.unsafe_buffer_pointer())
                except Exception:
                    old[n] = (v, None)
        out, = exe.run_steps(main_p, feed=groups[1], fetch_list=[loss],
                             fetch_policy='stack')
        save['g1'] = np.asarray(out)

        old_deleted = sum(1 for v, _ in old.values() if v.is_deleted())
        aliased = 0
        for n, (v, ptr) in old.items():
            nv = scope.get(n)
            if ptr is None or not isinstance(nv, jax.Array):
                continue
            try:
                if nv.unsafe_buffer_pointer() == ptr:
                    aliased += 1
            except Exception:
                pass
        for n, v in sorted(scope._vars.items()):
            if v is not None:
                save['state_%s' % n] = np.asarray(v)
    np.savez(out_path, **save)

    cert = exe._donation_certs.get(main_p._uid)
    donated_entries = 0
    for p in glob.glob(os.path.join(cache_dir, 'entries', '*.json')):
        try:
            with open(p) as f:
                donated_entries += bool(json.load(f).get('donated'))
        except (OSError, ValueError):
            pass
    s = cc.stats()
    print('DONATION_STATS %s' % json.dumps({
        'cert_safe': bool(cert.safe) if cert is not None else None,
        'cert_reasons': list(cert.reasons) if cert is not None else [],
        'exec_hits': s['exec_hits'], 'misses': s['misses'],
        'xla_compiles_net': s['xla_compiles_net'],
        'donated_entries': donated_entries,
        'old_deleted': old_deleted, 'aliased_state': aliased,
        'state_total': len(old),
        'wall_s': round(time.perf_counter() - t0, 3)}))
    print('DONATION_OK')


if __name__ == '__main__':
    main()
