"""One pod-member incarnation for the ELASTIC (topology-resize) tests
(tests/test_elastic_pod.py, scripts/elastic_resume_smoke.py,
tools/chaos.py --pod N --resize).

usage: elastic_pod_worker.py CKPT_DIR DATA_FILE OUT_FILE TOTAL EVERY \
           [KILL_AT_STEP]
       elastic_pod_worker.py --make-data DATA_FILE NUM_RECORDS

env contract (set by the driver):
    PADDLE_TRAINERS / PADDLE_TRAINER_ID / PADDLE_COORDINATOR   pod shape
    PTPU_POD_RUN_ID     incarnation token (fresh per pod launch)
    PTPU_POD_HB_TIMEOUT watchdog heartbeat timeout (default 6s)

The difference from pod_ft_worker.py: this worker trains from a REAL
sharded data plane (ShardedFileReader over 1-record recordio chunks,
exactly-once journal) and is topology-elastic — it restores a pod
checkpoint written by ANY host count. The data layout makes the
per-step GLOBAL batch a topology-invariant SET: the global batch is
GLOBAL_BS records, chunks are strided per host (chunk j belongs to host
j %% N), and each host consumes GLOBAL_BS/N records per step, so step s
always trains chunks [s*GLOBAL_BS, (s+1)*GLOBAL_BS) — only the row
ORDER inside the batch depends on N. Mean loss and summed gradients are
row-permutation-invariant up to float accumulation, which is exactly
the resize parity contract: same-shape resume stays BIT-exact, resized
resume matches within float-accumulation tolerance while the rng step
stream and the exactly-once sample accounting stay exact. (The model
deliberately has no dropout: a per-ROW rng op would tie the mask to the
row order and break the permutation invariance.)

OUT_FILE lines (append, flushed per step):
    RESUME <step> <startup_s>        restore point of this incarnation
    TOPO <ckpt_hosts> <now_hosts>    topology this incarnation restored
    RESHARD <programs> <arrays> <stitch_s> <place_s>
    RESTRIDE <done> <progress> <total>   journal re-stride summary
    <step_idx> <loss>                replicated loss (identical on hosts)
    RECS <step_idx> <h1,h2,...>      sha256[:16] of each record trained
    STALL <ckpt_stall_pct>
    DONE <params_sha256>             (bit-comparable only without resize)
"""
import hashlib
import os
import struct
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GLOBAL_BS = 16
FEAT = 16
CLASSES = 5


def make_record(i):
    r = __import__('numpy').random.RandomState(9000 + i)
    feat = r.randn(FEAT).astype('<f4')
    lab = int(r.randint(0, CLASSES))
    return feat.tobytes() + struct.pack('<q', lab)


def rec_hash(rec):
    return hashlib.sha256(rec).hexdigest()[:16]


def make_data(path, num_records):
    """Write the dataset as 1-record chunks (chunk-granular stride =
    record-granular stride) plus a sidecar .hashes file the drivers use
    for the exactly-once epoch digest."""
    from paddle_tpu import recordio
    recs = [make_record(i) for i in range(int(num_records))]
    recordio.write_recordio(path, recs, max_chunk_bytes=1)
    with open(path + '.hashes', 'w') as f:
        for rec in recs:
            f.write(rec_hash(rec) + '\n')


if __name__ == '__main__' and len(sys.argv) > 1 \
        and sys.argv[1] == '--make-data':
    make_data(sys.argv[2], int(sys.argv[3]))
    sys.exit(0)

os.environ.setdefault('XLA_FLAGS', '--xla_force_host_platform_device_count=2')
os.environ['JAX_PLATFORMS'] = 'cpu'

from paddle_tpu.parallel import multihost  # noqa: E402

# join the pod BEFORE any backend use
N, RANK = multihost.init_distributed(platform='cpu')

import numpy as np                                           # noqa: E402
import paddle_tpu as fluid                                   # noqa: E402
from paddle_tpu.core.checkpoint import (                     # noqa: E402
    PodCheckpointManager, HostWatchdog)
from paddle_tpu.parallel import shard_parameter              # noqa: E402
from paddle_tpu.parallel.mesh import make_mesh               # noqa: E402
from paddle_tpu.parallel.compiler import CompiledProgram     # noqa: E402
from paddle_tpu.reader.sharded import (                      # noqa: E402
    ShardedFileReader, restride_journal)
from paddle_tpu.testing import faults                        # noqa: E402


def build(seed=17):
    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = seed
    with fluid.program_guard(main_p, startup_p):
        x = fluid.layers.data(name='x', shape=[FEAT], dtype='float32')
        lab = fluid.layers.data(name='lab', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=32, act='relu',
                            param_attr=fluid.ParamAttr(name='fc1_w'))
        logits = fluid.layers.fc(h, size=CLASSES,
                                 param_attr=fluid.ParamAttr(name='fc2_w'))
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=lab))
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(loss)
    # composed sharding with genuinely cross-host shards: fc1_w
    # column-parallel over mp (within a host), fc2_w row-sharded over dp
    # (the axis that SPANS hosts); optimizer slots inherit (reshard.py)
    shard_parameter(main_p.global_block().var('fc1_w'), (None, 'mp'))
    shard_parameter(main_p.global_block().var('fc2_w'), ('dp', None))
    return main_p, startup_p, loss


def decode(rec):
    feat = np.frombuffer(rec[:4 * FEAT], '<f4')
    lab = struct.unpack('<q', rec[4 * FEAT:4 * FEAT + 8])[0]
    return feat, lab


def params_sha(program, scope):
    from paddle_tpu.io import _full_value
    from paddle_tpu.core.lod import unwrap
    h = hashlib.sha256()
    for name in sorted(v.name for v in program.list_vars() if v.persistable):
        val = scope.get(name)
        if val is not None:
            h.update(name.encode())
            h.update(np.ascontiguousarray(
                np.asarray(unwrap(_full_value(val)))).tobytes())
    return h.hexdigest()


def main():
    ckpt_dir, data_file, out_path = sys.argv[1], sys.argv[2], sys.argv[3]
    total, every = int(sys.argv[4]), int(sys.argv[5])
    kill_at = int(sys.argv[6]) if len(sys.argv) > 6 else 0
    if GLOBAL_BS % N:
        raise SystemExit('host count %d does not divide the global '
                         'batch %d' % (N, GLOBAL_BS))
    local_bs = GLOBAL_BS // N

    import time
    run_id = multihost.pod_run_id()
    hb_timeout = float(os.environ.get('PTPU_POD_HB_TIMEOUT', '6'))

    main_p, startup_p, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)
    mesh = make_mesh(axes={'dp': N, 'mp': 2})
    prog = CompiledProgram(main_p).with_data_parallel(loss_name=loss.name,
                                                      mesh=mesh)

    t0 = time.perf_counter()
    mgr = PodCheckpointManager(ckpt_dir, rank=RANK, num_hosts=N,
                               every_steps=every, keep_last_n=3,
                               commit_timeout_s=30,
                               heartbeat_interval_s=0.2, run_id=run_id,
                               topology={'dp': N, 'mp': 2})
    wd = HostWatchdog(ckpt_dir, rank=RANK, num_hosts=N,
                      timeout_s=hb_timeout, run_id=run_id,
                      action='exit', exit_code=3).start()
    info = mgr.restore(executor=exe, program=prog)
    startup_s = time.perf_counter() - t0
    step = int(info['step']) if info else 0

    out = open(out_path, 'a')

    def emit(line):
        out.write(line + '\n')
        out.flush()
        os.fsync(out.fileno())

    # -- data plane: same-shape resumes continue THIS rank's journal at
    # its checkpointed position; a resize re-strides EVERY old host's
    # journal onto the new disjoint cover (no chunk replayed, none lost)
    my_journal = os.path.join(
        ckpt_dir, 'journal-%s-h%dof%d.jsonl' % (run_id, RANK, N))

    def rebase(tj):
        # the checkpoint records the journal's ABSOLUTE path, but the
        # journal files live inside ckpt_dir, so THIS tree's copy is
        # authoritative: prefer basename-in-this-dir whenever it exists
        # (identical to the recorded path on a normal in-place resume;
        # on a copied/moved tree it keeps the resume from truncating
        # the ORIGINAL tree's journal). run_id in the filename keeps
        # incarnations distinct. Fall back to the recorded path for
        # journals stored outside the checkpoint dir.
        if not tj or not tj.get('path'):
            return tj
        local = os.path.join(ckpt_dir, os.path.basename(tj['path']))
        return dict(tj, path=local) if os.path.exists(local) else tj

    journal_path, journal_limit = my_journal, None
    if info is not None:
        old_hosts = int(info.get('pod_num_hosts') or N)
        journals = {r: rebase(tj)
                    for r, tj in (info.get('task_journals') or {}).items()}
        if old_hosts == N and journals.get(RANK):
            journal_path = journals[RANK]['path']
            journal_limit = journals[RANK]['position']
        else:
            counts = restride_journal(
                [journals.get(r) for r in range(old_hosts)],
                [data_file], N, RANK, my_journal)
            emit('RESTRIDE %d %d %d' % (counts['done'],
                                        counts['progress'],
                                        counts['total']))
    reader = ShardedFileReader(
        [data_file], shard_id=RANK, num_shards=N,
        journal_path=journal_path, journal_limit=journal_limit,
        progress_every=1, holder_id='shard-%d-of-%d' % (RANK, N))
    mgr.task_service = reader

    emit('RESUME %d %.3f' % (step, startup_s))
    emit('TOPO %d %d' % (int(info['pod_num_hosts']) if info else N, N))
    rs = (info or {}).get('reshard') or {}
    emit('RESHARD %d %d %.4f %.4f'
         % (rs.get('programs', 0), rs.get('arrays', 0),
            (info or {}).get('stitch_s', 0.0), rs.get('place_s', 0.0)))

    stream = [None]

    def next_batch():
        xs, labs, hashes = [], [], []
        while len(xs) < local_bs:
            if stream[0] is None:
                stream[0] = reader.records()
            try:
                rec = next(stream[0])
            except StopIteration:
                stream[0] = None      # epoch complete: start the next
                continue
            feat, lab = decode(rec)
            xs.append(feat)
            labs.append(lab)
            hashes.append(rec_hash(rec))
        return (np.stack(xs).astype(np.float32),
                np.asarray(labs, np.int64)[:, None], hashes)

    while step < total:
        xs, labs, hashes = next_batch()
        l, = exe.run(prog, feed={'x': xs, 'lab': labs},
                     fetch_list=[loss], checkpoint=mgr)
        step += 1
        emit('%d %.17g' % (step - 1, float(np.asarray(l).reshape(-1)[0])))
        emit('RECS %d %s' % (step - 1, ','.join(hashes)))
        if kill_at and step >= kill_at:
            # die at a COMMITTED boundary: wait for THIS step's
            # POD_COMMIT on disk so the resize provably resumes here —
            # unless the boundary was skipped/abandoned (writer busy on
            # some host), in which case the newest OLDER commit is the
            # resume point and waiting longer would change nothing
            from paddle_tpu.core.checkpoint import _POD_COMMIT, _PREFIX
            t_kill = time.time()
            deadline = t_kill + 30
            pc = os.path.join(ckpt_dir, '%s%d' % (_PREFIX, step),
                              _POD_COMMIT)
            while time.time() < deadline and not os.path.exists(pc):
                if mgr._idle.is_set() and time.time() > t_kill + 2.0:
                    break      # this host's write concluded without a
                    # pod commit (skip/abandon): nothing more will land
                time.sleep(0.01)
            faults.kill_self()
        faults.maybe_kill_at_step(step)
    mgr.save(prog, fluid.global_scope(), step, blocking=True, executor=exe)
    st = exe._dispatch_stats
    emit('STALL %.4f' % (100.0 * st['ckpt_stall_s'] / st['run_s']
                         if st['run_s'] else 0.0))
    emit('DONE %s' % params_sha(main_p, fluid.global_scope()))
    mgr.barrier('done', timeout_s=60)
    wd.stop()
    reader.close()
    mgr.close()


if __name__ == '__main__':
    main()
