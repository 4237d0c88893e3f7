#!/usr/bin/env python
"""Smoke the `serve.py bench` CLI on a tiny multi-bucket artifact
(ISSUE 1 CI satellite): build a small model, export batch buckets {1, 4},
then drive the dynamic batcher from a fresh framework-free process.

    python scripts/serve_bench_smoke.py

Exits non-zero if the bench fails or reports no throughput.
"""
import json
import os
import subprocess
import sys
import tempfile

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.inference import (Config, create_predictor,  # noqa: E402
                                  export_compiled)


def main():
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        img = fluid.layers.data(name='img', shape=[16], dtype='float32')
        out = fluid.layers.fc(fluid.layers.fc(img, 32, act='relu'), 4,
                              act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)
    with tempfile.TemporaryDirectory() as d:
        model_dir = os.path.join(d, 'model')
        art_dir = os.path.join(d, 'artifact')
        fluid.io.save_inference_model(model_dir, ['img'], [out], exe,
                                      main_p)
        cfg = Config(model_dir)
        cfg.disable_gpu()
        pred = create_predictor(cfg)
        sample = np.random.RandomState(0).randn(4, 16).astype(np.float32)
        export_compiled(pred, [sample], art_dir, batch_sizes=[1, 4])

        in_path = os.path.join(d, 'in.npz')
        np.savez(in_path, img=sample[:1])
        serve_py = os.path.join(REPO, 'paddle_tpu', 'inference', 'serve.py')
        r = subprocess.run(
            [sys.executable, serve_py, 'bench', art_dir, in_path, '16'],
            capture_output=True, text=True, timeout=600)
        sys.stdout.write(r.stdout)
        sys.stderr.write(r.stderr)
        if r.returncode != 0:
            return r.returncode
        stats = json.loads(
            [l for l in r.stdout.splitlines() if l.strip()][-1])
        if stats['req_s'] <= 0:
            print('serve.py bench reported no throughput', file=sys.stderr)
            return 1
    print('serve bench smoke OK (%.0f req/s, p99 %.2f ms)'
          % (stats['req_s'], stats['p99_ms']))
    return 0


if __name__ == '__main__':
    sys.exit(main())
