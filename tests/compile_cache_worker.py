"""Subprocess worker for test_compile_cache.py and warm_start_smoke.py:
one autoscaled-replica "cold start". Builds a small deterministic train
program, runs it through the persistent compile cache (run() steps plus a
run_steps multi-step group), saves every fetch to an npz, and prints the
cache counters as a JSON line:

    python compile_cache_worker.py CACHE_DIR OUT.npz

The caller runs it twice against one cache dir: run 1 is the cold miss
path (trace + compile + persist), run 2 must perform ZERO XLA compiles
for the cached entries and produce byte-identical fetches — the ISSUE 5
acceptance bar.
"""
import json
import os
import sys


def main():
    cache_dir, out_path = sys.argv[1], sys.argv[2]
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['PTPU_COMPILE_CACHE'] = '1'
    os.environ['JAX_COMPILATION_CACHE_DIR'] = cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)

    import time

    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import compile_cache as cc

    t0 = time.perf_counter()

    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 11
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name='x', shape=[6], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(x, size=8, act='relu')
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square(pred - y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)

    rng = np.random.RandomState(0)
    feeds = [{'x': rng.randn(4, 6).astype(np.float32),
              'y': rng.randn(4, 1).astype(np.float32)} for _ in range(6)]

    scope = fluid.core.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    save = {}
    with fluid.scope_guard(scope):
        exe.run(startup)
        for i in range(3):
            out, = exe.run(main_p, feed=feeds[i], fetch_list=[loss])
            save['run%d' % i] = np.asarray(out)
        # a K=3 multi-step dispatch rides the same persistent cache
        group = {'x': np.stack([f['x'] for f in feeds[3:]]),
                 'y': np.stack([f['y'] for f in feeds[3:]])}
        stacked, = exe.run_steps(main_p, feed=group, fetch_list=[loss],
                                 fetch_policy='stack')
        save['steps'] = np.asarray(stacked)
    np.savez(out_path, **save)

    s = cc.stats()
    out = {k: s[k] for k in ('exec_hits', 'hlo_hits', 'misses', 'compiles',
                             'corrupt', 'xla_compiles', 'xla_pcache_hits',
                             'xla_compiles_net')}
    out['compile_s'] = round(s['compile_s'], 3)
    out['wall_s'] = round(time.perf_counter() - t0, 3)
    print('CC_STATS %s' % json.dumps(out))
    print('CC_OK')


if __name__ == '__main__':
    main()
