#!/usr/bin/env python
"""Data-plane smoke (ISSUE 9 acceptance): sharded streaming input must
saturate the prefetch ring.

1) Feeder A/B on the synthetic image pipeline (dataset/synthetic.py):
   the SAME shards and the SAME decode fn (zlib + numpy normalize + a
   modeled remote-fetch latency), read serially vs through the decode
   pool. Asserts pooled >= 3x serial samples/s AND bit-identical epoch
   contents (the pool decodes out of order but delivers in order).
2) Exactly-once resume: kill the pooled epoch mid-flight, resume from
   the elastic journal with a fresh reader — the union of deliveries is
   exactly one epoch.
3) Real image train loop (smallnet conv path) driven by
   MultiStepTrainer over a prefetch ring fed by the pooled reader:
   training_report() must show host-stall < 2%.
"""
import os
import sys
import time
import hashlib
import tempfile

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import numpy as np  # noqa: E402

NUM_SHARDS = int(os.environ.get('PTPU_DP_SHARDS', '4'))
SAMPLES_PER_SHARD = int(os.environ.get('PTPU_DP_SAMPLES', '128'))
WORKERS = int(os.environ.get('PTPU_DP_WORKERS', '8'))
LATENCY_MS = float(os.environ.get('PTPU_DP_LATENCY_MS', '3.0'))
MODE = os.environ.get('PTPU_DP_MODE', 'thread')
MIN_SPEEDUP = float(os.environ.get('PTPU_DP_MIN_SPEEDUP', '3.0'))


def epoch_digest_and_rate(reader_callable, decode_inline=None):
    """Drain one epoch; returns (sha256 hexdigest, samples/s, n)."""
    h = hashlib.sha256()
    n = 0
    t0 = time.perf_counter()
    for item in reader_callable():
        if decode_inline is not None:
            item = decode_inline(item)
        img, label = item
        h.update(img.tobytes())
        h.update(label.tobytes())
        n += 1
    dt = time.perf_counter() - t0
    return h.hexdigest(), n / dt, n


def main():
    from paddle_tpu.dataset import synthetic
    from paddle_tpu.reader.sharded import ShardedFileReader

    tmp = tempfile.mkdtemp(prefix='ptpu_dp_smoke_')
    files = synthetic.write_shards(
        tmp, num_shards=NUM_SHARDS, samples_per_shard=SAMPLES_PER_SHARD,
        seed=7)
    decode = synthetic.make_decode_fn(latency_s=LATENCY_MS * 1e-3)
    total = NUM_SHARDS * SAMPLES_PER_SHARD

    # -- 1) serial vs pooled A/B -------------------------------------------
    serial = ShardedFileReader(files)
    d_serial, r_serial, n = epoch_digest_and_rate(serial.records,
                                                  decode_inline=decode)
    assert n == total, (n, total)

    pooled_src = ShardedFileReader(files)
    pooled = pooled_src.pooled(decode, num_workers=WORKERS, mode=MODE)
    d_pooled, r_pooled, n = epoch_digest_and_rate(pooled)
    assert n == total, (n, total)
    stats = pooled.feeder_stats()

    speedup = r_pooled / r_serial
    print('feeder A/B: serial %.0f samples/s, pooled(%d %s) %.0f '
          'samples/s -> %.2fx (occupancy %.2f, decode %.2f ms avg, '
          'max in-flight %d)'
          % (r_serial, WORKERS, MODE, r_pooled, speedup,
             stats['occupancy'], stats['decode_ms_avg'],
             stats['max_inflight']))
    assert d_serial == d_pooled, 'epoch contents differ serial vs pooled'
    print('epoch contents bit-identical: sha256 %s' % d_serial[:16])
    assert speedup >= MIN_SPEEDUP, (
        'pooled feeder %.2fx < %.1fx floor' % (speedup, MIN_SPEEDUP))

    # -- 2) exactly-once resume through the elastic journal ----------------
    jp = os.path.join(tmp, 'feed.journal')
    r1 = ShardedFileReader(files, journal_path=jp, progress_every=1)
    g = r1.pooled(decode, num_workers=4, mode=MODE)()
    killed_at = total // 3
    seen = [next(g) for _ in range(killed_at)]
    g.close()   # simulated kill: leases release, journal keeps progress
    r1.close()
    r2 = ShardedFileReader(files, journal_path=jp, progress_every=1)
    rest = list(r2.pooled(decode, num_workers=4, mode=MODE)())
    r2.close()
    assert len(seen) + len(rest) == total, (len(seen), len(rest), total)
    h = hashlib.sha256()
    for img, label in seen + rest:
        h.update(img.tobytes())
        h.update(label.tobytes())
    # delivery order is deterministic, so resume must CONTINUE the same
    # stream: concatenated digests match the uninterrupted epoch
    assert h.hexdigest() == d_serial, 'kill+resume epoch diverged'
    print('exactly-once resume: %d + %d = %d samples, digest matches'
          % (len(seen), len(rest), total))

    # -- 3) real image train loop: host-stall < 2% -------------------------
    import paddle_tpu as fluid
    from paddle_tpu.reader.pipeline import PyReader
    from paddle_tpu.parallel import MultiStepTrainer
    from models.smallnet import build_train_net

    batch = 32
    k = 4
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        images, label, loss, acc = build_train_net()

    train_src = ShardedFileReader(files)
    train_pooled = train_src.pooled(decode, num_workers=WORKERS, mode=MODE)
    batched = fluid.reader.batch(train_pooled, batch, drop_last=True)

    py_reader = PyReader([images, label], capacity=8)
    py_reader.decorate_paddle_reader(batched)
    py_reader.prefetch_to_device(k, depth=2)

    trainer = MultiStepTrainer(main_p, steps_per_dispatch=k,
                               fetch_list=[loss])
    trainer.startup(startup_p)
    losses = []
    for epoch in range(2):
        for fetches in trainer.iter_epoch(py_reader):
            losses.append(float(np.asarray(fetches[0]).reshape(-1)[-1]))
    from paddle_tpu import profiler
    report = profiler.training_report()
    exe_rows = [s for name, s in report.items()
                if name != 'feeders' and 'dispatches' in s]
    assert exe_rows, 'no training source registered'
    stall_pct = exe_rows[0].get('host_stall_pct', 100.0)
    print('train loop: %d dispatches, %d losses, host-stall %.2f%%'
          % (exe_rows[0]['dispatches'], len(losses), stall_pct))
    assert np.isfinite(losses).all()
    assert stall_pct < 2.0, 'host-stall %.2f%% >= 2%%' % stall_pct
    feeders = report.get('feeders', {})
    assert feeders, 'feeder source missing from training_report'

    print('DATA PLANE SMOKE OK: %.2fx feeder speedup, bit-identical '
          'epochs, exactly-once resume, host-stall %.2f%%'
          % (speedup, stall_pct))


if __name__ == '__main__':
    main()
