"""Multi-step dispatch smoke for CI (ISSUE 2): on CPU,

1. SmallNet, K=4: run_steps through a prefetch_to_device ring must track
   8 sequential single-step run() calls step for step (losses AND
   params). Tolerance note: XLA:CPU compiles CONV kernels inside while
   bodies through a different code path than at top level, so conv
   models match to ~1e-6 relative on CPU rather than bit-for-bit;
   matmul-based models ARE bit-identical (tests/test_multi_step.py
   asserts exact equality across dropout/momentum/grad-merge nets).
2. fc proxy, K=16: same-session dispatch-rate A/B must improve >= 3x —
   the CPU dispatch-overhead proxy for per-dispatch cost amortization
   (smallnet itself is NOT used for the CPU speedup check: XLA:CPU runs
   conv scan bodies ~10x slower than at top level, PERF_NOTES round 6;
   on the accelerator the conv model amortizes like any other).

Exits non-zero on any violation. Runtime: ~30 s on 2 CPU cores.
"""
import json
import os
import sys
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def smallnet_bit_identity():
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    from models.smallnet import build_train_net

    batch, k, steps = 8, 4, 8
    rng = np.random.RandomState(0)
    xs = [rng.randn(batch, 3, 32, 32).astype(np.float32)
          for _ in range(steps)]
    labs = [rng.randint(0, 10, (batch, 1)) for _ in range(steps)]

    def build():
        with unique_name.guard():
            main_p, startup_p = fluid.Program(), fluid.Program()
            main_p.random_seed = startup_p.random_seed = 7
            with fluid.program_guard(main_p, startup_p):
                _img, _lab, loss, _acc = build_train_net()
        return main_p, startup_p, loss

    main_p, startup_p, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        seq = [np.asarray(exe.run(main_p,
                                  feed={'data': xs[i], 'label': labs[i]},
                                  fetch_list=[loss])[0]).reshape(-1)
               for i in range(steps)]
        p_seq = {v.name: np.asarray(scope.get(v.name)).copy()
                 for v in main_p.list_vars() if v.persistable
                 and scope.get(v.name) is not None}

    main_p, startup_p, loss = build()
    reader = None
    with fluid.program_guard(main_p, startup_p):
        pass
    from paddle_tpu.reader.pipeline import PyReader
    dvars = [main_p.global_block().var('data'),
             main_p.global_block().var('label')]
    reader = PyReader(dvars, capacity=4).prefetch_to_device(k)
    reader.decorate_tensor_provider(lambda: iter(
        [{'data': x, 'label': l} for x, l in zip(xs, labs)]))
    exe2 = fluid.Executor(fluid.CPUPlace())
    scope2 = fluid.core.Scope()
    multi = []
    with fluid.scope_guard(scope2):
        exe2.run(startup_p)
        reader.start()
        for _ in range(steps // k):
            out, = exe2.run_steps(main_p, reader=reader, fetch_list=[loss],
                                  steps=k, fetch_policy='stack')
            multi.extend(np.asarray(out).reshape(k, -1))
        reader.reset()
        p_multi = {v.name: np.asarray(scope2.get(v.name)).copy()
                   for v in main_p.list_vars() if v.persistable
                   and scope2.get(v.name) is not None}

    for i, (s, m) in enumerate(zip(seq, multi)):
        if not np.allclose(s, m, rtol=1e-5, atol=1e-6):
            raise SystemExit('smallnet K=%d step %d loss mismatch: %r vs %r'
                             % (k, i, s, m))
    if set(p_seq) != set(p_multi):
        raise SystemExit('smallnet K=%d persistable name sets differ' % k)
    for name in p_seq:
        if not np.allclose(p_seq[name], p_multi[name],
                           rtol=1e-4, atol=2e-5):
            raise SystemExit(
                'smallnet K=%d persistable %r mismatch (max abs diff %g)'
                % (k, name, np.abs(p_seq[name] - p_multi[name]).max()))
    return {'smoke': 'smallnet_bit_identity', 'k': k, 'steps': steps,
            'ok': True}


def fc_dispatch_ab():
    import paddle_tpu as fluid
    import jax.numpy as jnp

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        x = fluid.layers.data(name='x', shape=[64], dtype='float32')
        lab = fluid.layers.data(name='lab', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=128, act='relu')
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=fluid.layers.fc(h, 10), label=lab))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)
    rng = np.random.RandomState(0)
    feed = {'x': jnp.asarray(rng.randn(32, 64), jnp.float32),
            'lab': jnp.asarray(rng.randint(0, 10, (32, 1)), jnp.int32)}
    k = 16
    stacked = {n: jnp.stack([v] * k) for n, v in feed.items()}

    for _ in range(4):
        out = exe.run(main_p, feed=feed, fetch_list=[loss],
                      return_numpy=False)
    np.asarray(out[0])
    t0 = time.perf_counter()
    n = 60
    for _ in range(n):
        out = exe.run(main_p, feed=feed, fetch_list=[loss],
                      return_numpy=False)
    np.asarray(out[0])
    single_ms = (time.perf_counter() - t0) / n * 1e3

    for _ in range(2):
        out = exe.run_steps(main_p, feed=stacked, fetch_list=[loss],
                            steps=k, return_numpy=False)
    np.asarray(out[0])
    t0 = time.perf_counter()
    d = 10
    for _ in range(d):
        out = exe.run_steps(main_p, feed=stacked, fetch_list=[loss],
                            steps=k, return_numpy=False)
    np.asarray(out[0])
    multi_ms = (time.perf_counter() - t0) / (d * k) * 1e3

    speedup = single_ms / multi_ms
    line = {'smoke': 'fc_dispatch_ab', 'k': k,
            'single_ms_step': round(single_ms, 3),
            'multi_ms_step': round(multi_ms, 3),
            'speedup': round(speedup, 2)}
    if speedup < 3.0:
        line['ok'] = False
        print(json.dumps(line))
        raise SystemExit(
            'multi-step dispatch speedup %.2fx < 3x acceptance floor'
            % speedup)
    line['ok'] = True
    return line


def main():
    print(json.dumps(smallnet_bit_identity()), flush=True)
    print(json.dumps(fc_dispatch_ab()), flush=True)
    print('multi-step smoke OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
