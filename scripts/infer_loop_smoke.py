"""Bulk-inference loop smoke for CI (ISSUE 3), mirroring
multi_step_smoke.py: on CPU,

1. fc artifact, K=8: CompiledPredictor.run_batches must match 8
   sequential run() calls BIT FOR BIT (matmul model — XLA compiles
   matmul scan bodies identically to top-level code; conv models round
   to ~1e-6 on XLA:CPU, PERF_NOTES.md).
2. fc artifact, K=32: same-session dispatch-rate A/B — per-batch time
   through ONE run_batches(K) dispatch must beat sequential run() calls
   by >= 3x. This is a CPU proxy: only the per-call host cost is
   amortizable on CPU; what it buys on the chip is not measured.

Exits non-zero on any violation. Runtime: ~15 s on 2 CPU cores.
"""
import json
import os
import sys
import tempfile
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _export_fc_artifact(art_dir):
    import paddle_tpu as fluid
    from paddle_tpu.inference import Config, create_predictor, export_compiled

    model_dir = os.path.join(os.path.dirname(art_dir), 'model')
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[64], dtype='float32')
        h = fluid.layers.fc(x, 128, act='relu')
        out = fluid.layers.fc(h, 10, act='softmax')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.io.save_inference_model(model_dir, ['x'], [out], exe, main)
    cfg = Config(model_dir)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    sample = np.random.RandomState(0).randn(32, 64).astype(np.float32)
    export_compiled(pred, [sample], art_dir)
    return sample


def bit_identity(served, sample):
    rng = np.random.RandomState(1)
    xs = [rng.randn(*sample.shape).astype(np.float32) for _ in range(8)]
    seq = [served.run([x])[0] for x in xs]
    bulk = served.run_batches([[x] for x in xs])
    for i, (s, b) in enumerate(zip(seq, bulk)):
        if not np.array_equal(s, b[0]):
            raise SystemExit(
                'run_batches batch %d mismatch: max abs diff %g'
                % (i, np.abs(s - b[0]).max()))
    return {'smoke': 'run_batches_bit_identity', 'k': len(xs), 'ok': True}


def dispatch_ab(served, sample, attempts=2):
    """Best-of-N same-session A/B (a cold first jit-dispatch or a loaded
    CI host can depress one round; the floor is 3x with ~4x typical)."""
    k = 32
    batches = [[sample]] * k
    served.run([sample])        # warm the single-batch executable
    served.run_batches(batches)  # warm the K-group executable
    best = None
    for _ in range(attempts):
        t0 = time.perf_counter()
        n = 60
        for _ in range(n):
            served.run([sample])
        seq_ms = (time.perf_counter() - t0) / n * 1e3

        t0 = time.perf_counter()
        d = 6
        for _ in range(d):
            served.run_batches(batches)
        bulk_ms = (time.perf_counter() - t0) / (d * k) * 1e3
        if best is None or seq_ms / bulk_ms > best[0]:
            best = (seq_ms / bulk_ms, seq_ms, bulk_ms)
    speedup, seq_ms, bulk_ms = best
    line = {'smoke': 'infer_loop_dispatch_ab', 'k': k,
            'seq_ms_batch': round(seq_ms, 3),
            'bulk_ms_batch': round(bulk_ms, 3),
            'speedup': round(speedup, 2)}
    if speedup < 3.0:
        line['ok'] = False
        print(json.dumps(line))
        raise SystemExit(
            'bulk-inference dispatch speedup %.2fx < 3x acceptance floor'
            % speedup)
    line['ok'] = True
    return line


def main():
    from paddle_tpu.inference import load_compiled
    with tempfile.TemporaryDirectory() as d:
        art = os.path.join(d, 'artifact')
        sample = _export_fc_artifact(art)
        served = load_compiled(art)
        print(json.dumps(bit_identity(served, sample)), flush=True)
        print(json.dumps(dispatch_ab(served, sample)), flush=True)
        print(json.dumps({'smoke': 'bulk_stats',
                          **served.bulk_stats()}), flush=True)
    print('infer loop smoke OK')
    return 0


if __name__ == '__main__':
    sys.exit(main())
