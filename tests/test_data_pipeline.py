"""Data pipeline: reader decorators, py_reader queue/EOF semantics,
DataFeeder, datasets (ref: test_py_reader_using_executor.py, reader tests)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import reader as reader_mod


def test_decorators():
    def r():
        return iter(range(10))
    b = reader_mod.batch(lambda: iter(range(10)), 3)
    batches = list(b())
    assert batches[0] == [0, 1, 2] and batches[-1] == [9]
    s = reader_mod.shuffle(lambda: iter(range(100)), 50)
    assert sorted(s()) == list(range(100))
    f = reader_mod.firstn(lambda: iter(range(100)), 5)
    assert list(f()) == [0, 1, 2, 3, 4]
    c = reader_mod.chain(lambda: iter([1]), lambda: iter([2]))
    assert list(c()) == [1, 2]
    m = reader_mod.map_readers(lambda a: a * 2, lambda: iter([1, 2]))
    assert list(m()) == [2, 4]


def test_bucket_by_length():
    samples = [[0] * l for l in [2, 9, 3, 8, 2, 9]]
    br = reader_mod.bucket_by_length(lambda: iter(samples), len,
                                     [4, 16], 2)
    batches = list(br())
    for b in batches:
        lens = [len(s) for s in b]
        assert all(l <= 4 for l in lens) or all(4 < l <= 16 for l in lens)


def test_py_reader_trains_with_eof():
    reader = fluid.layers.py_reader(
        capacity=8, shapes=[(-1, 4), (-1, 1)], dtypes=['float32', 'int64'])
    x, label = fluid.layers.read_file(reader)
    logits = fluid.layers.fc(input=x, size=3)
    loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
        logits=logits, label=label))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)

    def data():
        for i in range(7):
            yield [(np.random.rand(4).astype(np.float32),
                    np.array([i % 3], np.int64)) for _ in range(6)]

    reader.decorate_paddle_reader(data)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())

    for epoch in range(2):
        reader.start()
        steps = 0
        while True:
            try:
                l, = exe.run(fetch_list=[loss])
                steps += 1
            except fluid.core.EOFException:
                reader.reset()
                break
        assert steps == 7, steps


def test_prefetch_ring_groups_and_tail():
    """prefetch_to_device(K): the feeder thread stacks K host batches
    into one [K, ...] device buffer per var; EOF flushes a partial tail
    group; the drained ring raises EOFException."""
    import jax
    from paddle_tpu.reader.pipeline import PyReader
    x = fluid.layers.data('px', shape=[4], dtype='float32')
    r = PyReader([x], capacity=8).prefetch_to_device(4, depth=2)

    def gen():
        for i in range(10):
            yield {'px': np.full((2, 4), i, np.float32)}

    r.decorate_tensor_provider(lambda: gen())
    r.start()
    groups = []
    while True:
        try:
            groups.append(r._next_group())
        except fluid.core.EOFException:
            break
    assert [k for _, k in groups] == [4, 4, 2]
    g0 = groups[0][0]['px']
    assert isinstance(g0, jax.Array) and g0.shape == (4, 2, 4)
    # stacked values preserve batch order
    np.testing.assert_array_equal(np.asarray(g0)[:, 0, 0], [0, 1, 2, 3])
    assert groups[2][0]['px'].shape == (2, 2, 4)
    assert r.prefetch_stats['groups'] == 3
    assert r.prefetch_stats['tail_groups'] == 1
    r.reset()


def test_prefetch_ring_stacks_device_arrays_device_side():
    """Batches already on device stack with jnp (no per-batch D2H
    pull)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.reader.pipeline import PyReader
    x = fluid.layers.data('pd', shape=[3], dtype='float32')
    r = PyReader([x], capacity=4).prefetch_to_device(2)

    def gen():
        for i in range(4):
            yield {'pd': jnp.full((2, 3), float(i))}

    r.decorate_tensor_provider(lambda: gen())
    r.start()
    g, k = r._next_group()
    assert k == 2 and isinstance(g['pd'], jax.Array)
    np.testing.assert_array_equal(np.asarray(g['pd'])[:, 0, 0], [0., 1.])
    r.reset()


def test_prefetch_ring_mode_guards():
    """A prefetch-mode reader refuses per-batch pops (it stages groups),
    and a per-batch reader refuses _next_group; bad configs raise."""
    import pytest
    from paddle_tpu.reader.pipeline import PyReader
    x = fluid.layers.data('pg', shape=[2], dtype='float32')
    r = PyReader([x], capacity=4)
    with pytest.raises(ValueError, match='steps'):
        r.prefetch_to_device(0)
    with pytest.raises(ValueError, match='depth'):
        r.prefetch_to_device(2, depth=0)
    with pytest.raises(RuntimeError, match='prefetch'):
        r._next_group()
    r.prefetch_to_device(2)
    r.decorate_tensor_provider(
        lambda: iter([{'pg': np.zeros((1, 2), np.float32)}]))
    r.start()
    with pytest.raises(RuntimeError, match='run_steps'):
        r._next_batch()
    r.reset()


def test_prefetch_ring_rejects_lod_batches():
    """LoD host batches carry per-batch offsets — they cannot stack into
    one [K, ...] ring buffer, and the feeder surfaces a TypeError on the
    consumer side."""
    import pytest
    from paddle_tpu.reader.pipeline import PyReader
    x = fluid.layers.data('pl', shape=[1], dtype='int64', lod_level=1)
    r = PyReader([x], capacity=4).prefetch_to_device(2)

    def gen():
        lt = fluid.create_lod_tensor(np.zeros((3, 1), np.int64), [[2, 1]])
        yield {'pl': lt}
        yield {'pl': lt}

    r.decorate_tensor_provider(lambda: gen())
    r.start()
    with pytest.raises(TypeError, match='dense'):
        r._next_group()
    r.reset()


def test_prefetch_ring_midepoch_reset_no_interleave():
    """reset() mid-epoch then start(): the old feeder thread (captured
    dead queue) must never leak stale groups into the new epoch — the
    restarted ring yields the full sequence from 0, in order."""
    import time
    from paddle_tpu.reader.pipeline import PyReader
    x = fluid.layers.data('pr', shape=[2], dtype='float32')
    r = PyReader([x], capacity=4).prefetch_to_device(2, depth=1)

    def gen():
        for i in range(8):
            time.sleep(0.001)  # keep the feeder mid-flight at reset
            yield {'pr': np.full((1, 2), i, np.float32)}

    r.decorate_tensor_provider(lambda: gen())
    for _ in range(3):
        r.start()
        g, _k = r._next_group()  # consume ONE group, abandon the epoch
        np.testing.assert_array_equal(np.asarray(g['pr'])[:, 0, 0],
                                      [0, 1])
        r.reset()
    r.start()
    seen = []
    while True:
        try:
            g, _k = r._next_group()
            seen.extend(np.asarray(g['pr'])[:, 0, 0].astype(int))
        except fluid.core.EOFException:
            break
    assert seen == list(range(8)), seen
    r.reset()


@pytest.mark.slow
def test_prefetch_ring_threaded_stress():
    """Stress the ring's producer/consumer handoff: a jittery producer,
    shallow depth, many epochs — counts and order must hold, no
    deadlock."""
    import time
    from paddle_tpu.reader.pipeline import PyReader
    x = fluid.layers.data('ps', shape=[3], dtype='float32')
    r = PyReader([x], capacity=8).prefetch_to_device(3, depth=1)
    n_batches = 25
    rng = np.random.RandomState(0)

    def gen():
        for i in range(n_batches):
            if rng.rand() < 0.3:
                time.sleep(0.002)
            yield {'ps': np.full((2, 3), i, np.float32)}

    r.decorate_tensor_provider(lambda: gen())
    for _epoch in range(5):
        r.start()
        seen = []
        while True:
            try:
                g, k = r._next_group()
                if rng.rand() < 0.3:
                    time.sleep(0.002)  # slow consumer: ring backpressure
                seen.extend(np.asarray(g['ps'])[:, 0, 0].astype(int))
                assert k in (3, 1)
            except fluid.core.EOFException:
                break
        assert seen == list(range(n_batches))
        r.reset()


# -- sharded streaming input / decode worker pool (ISSUE 9) -----------------


def _write_shard_files(tmp_path, num_files=3, per_file=25):
    from paddle_tpu import recordio
    files, flat = [], []
    for fi in range(num_files):
        p = str(tmp_path / ('sh%02d.rio' % fi))
        recs = [('f%d-r%03d' % (fi, i)).encode() for i in range(per_file)]
        recordio.write_recordio(p, recs, max_chunk_bytes=80)  # multi-chunk
        files.append(p)
        flat.extend(recs)
    return files, flat


def test_shard_assignment_disjoint_coverage():
    """Across simulated hosts: every item lands on exactly one shard,
    shards balance to within one item, bad ids raise."""
    from paddle_tpu.reader.sharded import shard_assignment
    for n_items, n_shards in [(17, 4), (8, 8), (100, 7), (3, 5), (1, 1)]:
        items = ['it%d' % i for i in range(n_items)]
        parts = [shard_assignment(items, n_shards, s)
                 for s in range(n_shards)]
        assert sorted(sum(parts, [])) == sorted(items)
        for i, a in enumerate(parts):
            for b in parts[i + 1:]:
                assert not set(a) & set(b)
        sizes = [len(p) for p in parts]
        assert max(sizes) - min(sizes) <= 1
    with pytest.raises(ValueError, match='shard_id'):
        shard_assignment([1], 2, 2)
    with pytest.raises(ValueError, match='num_shards'):
        shard_assignment([1], 0, 0)


def test_pooled_map_deterministic_order():
    """Out-of-order decode (jittered latency), in-order delivery: the
    pooled stream is bit-identical to the serial map, twice (the pool
    is reusable per epoch), and the stats counters add up."""
    import time
    from paddle_tpu.reader import pooled_map

    def dec(x):
        time.sleep(0.001 * (x % 5))
        return x * 2

    pr = pooled_map(dec, lambda: iter(range(40)), num_workers=4)
    want = [x * 2 for x in range(40)]
    assert list(pr()) == want
    assert list(pr()) == want
    s = pr.feeder_stats()
    assert s['samples'] == 80 and s['workers'] == 4
    assert s['deaths'] == 0 and s['retries'] == 0
    assert s['decode_ms_avg'] > 0


def test_pooled_map_dead_worker_degrades():
    """A worker death warns loudly, its in-flight sample re-dispatches,
    the epoch completes in order on the survivors; when EVERY worker is
    dead the pool errors instead of deadlocking."""
    import threading
    import warnings as _w
    from paddle_tpu.reader import pooled_map, WorkerDied

    lk = threading.Lock()
    died = {'n': 0}

    def deadly(x):
        with lk:
            if x == 5 and died['n'] == 0:
                died['n'] = 1
                raise WorkerDied('chaos')
        return x

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter('always')
        pr = pooled_map(deadly, lambda: iter(range(30)), num_workers=3)
        assert list(pr()) == list(range(30))
    assert any('died' in str(x.message) for x in rec)
    assert pr.feeder_stats()['deaths'] == 1

    def everyone_dies(x):
        raise WorkerDied('total chaos')

    with _w.catch_warnings():
        _w.simplefilter('ignore')
        with pytest.raises(RuntimeError, match='workers died'):
            list(pooled_map(everyone_dies, lambda: iter(range(10)),
                            num_workers=2)())


def test_pooled_map_retries_flaky_then_errors_deterministic():
    """A flaky decode retries (with a RuntimeWarning) and the stream
    stays complete and ordered; a DETERMINISTIC decode failure exhausts
    its retry cap and raises with the record position."""
    import threading
    import warnings as _w
    from paddle_tpu.reader import pooled_map

    lk = threading.Lock()
    fails = {7: 1, 13: 2}

    def flaky(x):
        with lk:
            if fails.get(x, 0) > 0:
                fails[x] -= 1
                raise ValueError('flaky %d' % x)
        return x

    with _w.catch_warnings(record=True) as rec:
        _w.simplefilter('always')
        pr = pooled_map(flaky, lambda: iter(range(20)), num_workers=3)
        assert list(pr()) == list(range(20))
    assert any('retrying' in str(x.message) for x in rec)
    assert pr.feeder_stats()['retries'] == 3

    def rotten(x):
        if x == 3:
            raise ValueError('rotten record')
        return x

    with _w.catch_warnings():
        _w.simplefilter('ignore')
        with pytest.raises(RuntimeError, match='sample 3'):
            list(pooled_map(rotten, lambda: iter(range(10)),
                            num_workers=2)())


def test_pooled_map_backpressure_bound():
    """A slow consumer bounds the pool's memory: the source is never
    read more than `window` samples ahead of delivery, and the observed
    max in-flight respects the bound."""
    import time
    from paddle_tpu.reader import pooled_map

    produced = []

    def src():
        for i in range(60):
            produced.append(i)
            yield i

    window = 10
    pr = pooled_map(lambda x: x, src, num_workers=2, window=window)
    delivered = 0
    for v in pr():
        assert v == delivered
        delivered += 1
        if delivered % 7 == 0:
            time.sleep(0.005)  # slow consumer
        # the dispatcher may run at most `window` ahead of delivery
        assert len(produced) - delivered <= window + 1, (
            len(produced), delivered)
    assert delivered == 60
    assert pr.feeder_stats()['max_inflight'] <= window


def test_pooled_map_process_mode():
    """Process workers (fork): same ordered bit-identical delivery for
    GIL-bound decodes."""
    from paddle_tpu.reader import pooled_map
    pr = pooled_map(lambda x: x * 3, lambda: iter(range(30)),
                    num_workers=2, mode='process')
    assert list(pr()) == [x * 3 for x in range(30)]
    assert pr.feeder_stats()['samples'] == 30


def test_pooled_map_process_mode_unpicklable_result_is_loud():
    """mp.Queue's feeder thread silently DROPS values it cannot pickle
    (which would hang the pool forever) — workers pickle results
    themselves, so an unpicklable decode result surfaces as a loud
    per-sample error instead."""
    import threading
    import warnings as _w
    from paddle_tpu.reader import pooled_map

    def unpicklable(x):
        return threading.Lock()

    with _w.catch_warnings():
        _w.simplefilter('ignore')
        with pytest.raises(RuntimeError, match='failed'):
            list(pooled_map(unpicklable, lambda: iter(range(4)),
                            num_workers=2, mode='process')())


def test_sharded_reader_lazy_read_failure_retries(tmp_path):
    """A read_task_fn generator that fails MID-ITERATION (flaky mount)
    routes through the lease/failure machinery: the task backs off and
    retries, already-yielded records are not duplicated, and the epoch
    completes in order."""
    import warnings as _w
    from paddle_tpu.reader import ShardedFileReader
    files = []
    for i in range(2):
        p = str(tmp_path / ('f%d.txt' % i))
        with open(p, 'w') as f:
            f.write(''.join('l%d-%02d\n' % (i, j) for j in range(10)))
        files.append(p)
    state = {'failed': False}

    def read_lines(task):
        with open(task.path) as f:
            for j, line in enumerate(f):
                if task.path.endswith('f1.txt') and j == 5 \
                        and not state['failed']:
                    state['failed'] = True
                    raise IOError('flaky read')
                yield line.strip()

    r = ShardedFileReader(files, chunk_granular=False,
                          read_task_fn=read_lines, max_failures=3)
    r.service._backoff_base = 0.001  # keep the retry quick
    with _w.catch_warnings():
        _w.simplefilter('ignore')
        got = list(r())
    assert got == ['l0-%02d' % j for j in range(10)] \
        + ['l1-%02d' % j for j in range(10)]
    assert state['failed']  # the failure really fired


def test_sharded_reader_chunks_epochs_and_pool(tmp_path):
    """RecordIO shards split into chunk tasks; serial and pooled streams
    are bit-identical in deterministic (file, chunk) order; a drained
    reader starts the next epoch on the next call."""
    from paddle_tpu import recordio
    from paddle_tpu.reader import ShardedFileReader
    files, flat = _write_shard_files(tmp_path)
    assert len(recordio.chunk_index(files[0])) > 1  # chunk-granular

    r = ShardedFileReader(files,
                          journal_path=str(tmp_path / 'j.journal'),
                          progress_every=1)
    assert len(r.tasks) == sum(len(recordio.chunk_index(f))
                               for f in files)
    assert list(r()) == flat
    assert r.epoch_done
    assert list(r.pooled(lambda b: b, num_workers=4)()) == flat
    assert list(r())[:5] == flat[:5]  # third pass: a fresh epoch
    r.close()


def test_sharded_reader_disjoint_across_hosts(tmp_path):
    """Simulated 3-host pod: per-host readers cover the file set exactly
    once with no overlap — chunk tasks stride across hosts."""
    from paddle_tpu.reader import ShardedFileReader
    files, flat = _write_shard_files(tmp_path)
    streams = [list(ShardedFileReader(files, shard_id=s, num_shards=3)())
               for s in range(3)]
    assert sorted(sum(streams, [])) == sorted(flat)
    for i, a in enumerate(streams):
        for b in streams[i + 1:]:
            assert not set(a) & set(b)


def test_sharded_reader_exactly_once_kill_resume(tmp_path):
    """Mid-epoch kill (consumer torn down, leases released), then a
    FRESH reader on the same journal: the union of deliveries is exactly
    one epoch — no sample lost, none duplicated — and delivery order
    continues the same deterministic stream."""
    from paddle_tpu.reader import ShardedFileReader
    files, flat = _write_shard_files(tmp_path)
    jp = str(tmp_path / 'kill.journal')

    r1 = ShardedFileReader(files, journal_path=jp, progress_every=1)
    g = r1.pooled(lambda b: b, num_workers=2)()
    part = [next(g) for _ in range(31)]
    g.close()
    r1.close()

    r2 = ShardedFileReader(files, journal_path=jp, progress_every=1)
    rest = list(r2())
    r2.close()
    assert part + rest == flat  # exactly-once AND order-continuous


def test_sharded_reader_clean_stop_resume_same_reader(tmp_path):
    """In-session stop/resume on the SAME reader with a coarse journal
    cadence: a clean mid-epoch stop journals the exact delivered
    position and releases every held lease — including a task whose
    last record was read ahead but not yet delivered — so the next pass
    continues immediately (no lease-timeout stall), with zero replay
    and zero loss."""
    from paddle_tpu.reader import ShardedFileReader
    files, flat = _write_shard_files(tmp_path)
    r = ShardedFileReader(files, journal_path=str(tmp_path / 'cs.journal'),
                          progress_every=8, lease_timeout_s=3600.0)
    for stop_at in (17, 31):  # two successive partial passes
        g = r.pooled(lambda b: b, num_workers=2)()
        part = [next(g) for _ in range(stop_at)]
        g.close()
        assert part == flat[:stop_at]
        rest = list(r.pooled(lambda b: b, num_workers=2)())
        assert part + rest == flat  # zero replay, zero loss, in order
        assert r.epoch_done
    r.close()


def test_sharded_reader_journal_position_rewind(tmp_path):
    """journal_position()/journal_limit: rewinding the journal to a
    checkpointed position re-dispatches everything consumed after it —
    the checkpoint and the data accounting describe the same history."""
    from paddle_tpu.reader import ShardedFileReader
    files, flat = _write_shard_files(tmp_path)
    jp = str(tmp_path / 'rew.journal')

    r1 = ShardedFileReader(files, journal_path=jp, progress_every=1)
    g = iter(r1())
    for _ in range(10):
        next(g)
    pos = r1.journal_position()  # "checkpoint" here
    for _ in range(20):
        next(g)
    g.close()
    r1.close()

    r2 = ShardedFileReader(files, journal_path=jp, progress_every=1,
                           journal_limit=pos)
    rest = list(r2())
    r2.close()
    assert flat[:10] + rest == flat  # the 20 post-checkpoint replays


def test_sharded_reader_rejects_bad_config(tmp_path):
    from paddle_tpu.reader import ShardedFileReader
    files, _ = _write_shard_files(tmp_path, num_files=1)
    with pytest.raises(ValueError, match='empty file set'):
        ShardedFileReader([])
    with pytest.raises(ValueError, match='read_task_fn'):
        p = str(tmp_path / 'notrio.txt')
        with open(p, 'w') as f:
            f.write('hello\n')
        ShardedFileReader([p])


def test_shuffle_seed_reproducible():
    """shuffle(seed=): every invocation replays the same order; the
    default (no seed) still draws from global random — unchanged."""
    from paddle_tpu import reader as reader_mod
    r = reader_mod.shuffle(lambda: iter(range(50)), 16, seed=7)
    a, b = list(r()), list(r())
    assert a == b and sorted(a) == list(range(50))
    r2 = reader_mod.shuffle(lambda: iter(range(50)), 16, seed=8)
    assert list(r2()) != a
    legacy = reader_mod.shuffle(lambda: iter(range(50)), 16)
    assert sorted(legacy()) == list(range(50))


def test_pyreader_eof_rejoins_feeder_thread():
    """Satellite of ISSUE 9 (parallel/api.py:112): consuming EOF joins
    and clears the feeder thread, so epoch loops that never call
    reset() don't accumulate dead Thread objects."""
    from paddle_tpu.reader.pipeline import PyReader
    x = fluid.layers.data('pj', shape=[2], dtype='float32')
    r = PyReader([x], capacity=4)
    r.decorate_tensor_provider(
        lambda: iter([{'pj': np.zeros((1, 2), np.float32)}] * 3))
    for _ in range(5):  # repeated sessions, no reset() between them
        r.start()
        n = 0
        while True:
            try:
                r._next_batch()
                n += 1
            except fluid.core.EOFException:
                break
        assert n == 3
        assert r._thread is None  # rejoined at EOF, not left dangling


def test_feeder_stats_flow_into_training_report(tmp_path):
    """The pooled reader's decode counters surface through PyReader in
    profiler.training_report()'s feeder table, surviving batch()
    composition."""
    from paddle_tpu import profiler
    from paddle_tpu.reader import ShardedFileReader
    from paddle_tpu.reader.pipeline import PyReader
    from paddle_tpu.dataset import synthetic

    files = synthetic.write_shards(str(tmp_path), num_shards=2,
                                   samples_per_shard=16, seed=3)
    src = ShardedFileReader(files)
    pooled = src.pooled(synthetic.make_decode_fn(), num_workers=2)
    batched = fluid.reader.batch(pooled, 8, drop_last=True)
    assert callable(getattr(batched, 'feeder_stats', None))

    x = fluid.layers.data('fimg', shape=[3, 32, 32], dtype='float32')
    y = fluid.layers.data('flab', shape=[1], dtype='int64')
    r = PyReader([x, y], capacity=4)
    r.decorate_paddle_reader(batched)
    r.start()
    while True:
        try:
            r._next_batch()
        except fluid.core.EOFException:
            break
    report = profiler.feeder_report()
    mine = [s for name, s in report.items() if name.startswith('pyreader')
            and s.get('samples')]
    assert mine, report
    assert mine[0]['samples'] == 32
    assert mine[0]['workers'] == 2
    assert mine[0]['convert_ms'] > 0  # DataFeeder conversion accounted


def test_datasets_shapes():
    import paddle_tpu.dataset as ds
    img, lab = next(iter(ds.mnist.train()()))
    assert img.shape == (784,) and isinstance(lab, int)
    x, y = next(iter(ds.uci_housing.train()()))
    assert x.shape == (13,) and y.shape == (1,)
    toks, sent = next(iter(ds.imdb.train()()))
    assert isinstance(toks, list) and sent in (0, 1)
    src, tin, tout = next(iter(ds.wmt14.train(1000)()))
    assert len(tin) == len(src) + 1 and len(tout) == len(src) + 1


def test_data_feeder_lod():
    x = fluid.layers.data('x', shape=[1], dtype='int64', lod_level=1)
    y = fluid.layers.data('y', shape=[1], dtype='int64')
    feeder = fluid.DataFeeder(feed_list=[x, y], place=fluid.CPUPlace())
    feed = feeder.feed([([1, 2, 3], [0]), ([4, 5], [1])])
    lod_val = feed['x']
    assert lod_val.lod[0] == (0, 3, 5)
    assert np.asarray(lod_val.data).shape == (5, 1)
    assert feed['y'].shape == (2, 1)
