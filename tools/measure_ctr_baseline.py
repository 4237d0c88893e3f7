"""Measure the committed CTR denominator: the repo's own DeepFM trained on
the HOST CPU (fixed seed and config), giving the ctr_deepfm bench a
reproducible external baseline (VERDICT r4 weak #4 — the reference commits
no CTR number, and FLOPs proxies are meaningless for embedding-bound
work, so the honest denominator is the same model on the benchmark host's
CPU).

Run:  python tools/measure_ctr_baseline.py
Prints one JSON line; the accepted value is committed in BASELINE.md.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import numpy as np


def main():
    import paddle_tpu as fluid
    from models.deepfm import build_deepfm_train

    batch = int(os.environ.get('PTPU_CTR_BASE_BATCH', '4096'))
    steps = int(os.environ.get('PTPU_CTR_BASE_STEPS', '30'))

    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = 17
    with fluid.program_guard(main_p, startup_p):
        feeds, loss = build_deepfm_train()

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)

    rng = np.random.RandomState(0)
    feed = {}
    for name, shape, dtype, vocab in feeds:
        full = (batch,) + tuple(shape)
        if dtype.startswith('int'):
            feed[name] = rng.randint(0, vocab, full).astype(np.int32)
        elif vocab == 2:
            feed[name] = (rng.rand(*full) < 0.5).astype(np.float32)
        else:
            feed[name] = rng.randn(*full).astype(np.float32)

    for _ in range(4):  # compile + warmup
        l, = exe.run(main_p, feed=feed, fetch_list=[loss],
                     return_numpy=False)
    np.asarray(l)
    t0 = time.perf_counter()
    for _ in range(steps):
        l, = exe.run(main_p, feed=feed, fetch_list=[loss],
                     return_numpy=False)
    _ = float(np.asarray(l).reshape(-1)[0])
    dt = time.perf_counter() - t0
    print(json.dumps({
        'metric': 'ctr_deepfm_cpu_baseline_samples_s',
        'value': round(batch * steps / dt, 2), 'unit': 'samples/s',
        'batch': batch, 'steps': steps, 'seed': 17,
        'host': os.uname().machine}))


if __name__ == '__main__':
    main()
