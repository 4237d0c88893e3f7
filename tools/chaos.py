"""Chaos harness for fault-tolerant training (ISSUE 6 single-host,
ISSUE 10 pod mode): repeatedly SIGKILL a trainer at random step
boundaries — optionally corrupting the newest checkpoint between
incarnations — and verify that every incarnation's losses and the final
params BIT-MATCH an uninterrupted reference run.

    python tools/chaos.py                        # 3 kill rounds, no rot
    python tools/chaos.py --rounds 5 --corrupt random --seed 7
    python tools/chaos.py --total 48 --every 8 --keep
    python tools/chaos.py --pod 2                # pod mode: N processes,
                                                 # kill ONE random host
                                                 # per round, restart the
                                                 # WHOLE pod, assert
                                                 # bit/loss parity

Pod mode launches `--pod N` composed-mesh trainer processes
(tests/pod_ft_worker.py: dp spans hosts x mp within, sharded two-phase
pod checkpoints), SIGKILLs one random host mid-step, lets the survivors'
heartbeat watchdog exit them in bounded time, then restarts the full pod
on the same checkpoint dir — resume rides the shared warm compile cache
and must continue the loss stream bit-exactly on every host.

Per round: launch tests/checkpoint_kill_worker.py on a shared checkpoint
dir (it resumes from the newest committed checkpoint), let it train to a
randomly chosen step boundary, and let it SIGKILL itself there — racing
the async checkpoint writer exactly like a preemption. With --corrupt,
the newest checkpoint is then damaged (shard flip / manifest truncation
/ COMMIT removal) to prove restore falls back rather than loading it. A
final incarnation runs to completion and its params digest must equal
the reference's.

Exit 0: survived every round with bit parity. Exit 1: divergence or a
round that failed to make progress. ENOSPC/EIO write-path injection is
covered separately (in-process) by tests/test_checkpoint.py and
paddle_tpu/testing/faults.inject_write_errors.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, 'tests', 'checkpoint_kill_worker.py')


def _checkpoint_mod():
    """Load core/checkpoint.py standalone (stdlib+numpy only at import
    time) so the orchestrator never pays the framework/jax import."""
    spec = importlib.util.spec_from_file_location(
        'ptpu_chaos_checkpoint',
        os.path.join(REPO, 'paddle_tpu', 'core', 'checkpoint.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _faults_mod():
    spec = importlib.util.spec_from_file_location(
        'ptpu_chaos_faults',
        os.path.join(REPO, 'paddle_tpu', 'testing', 'faults.py'))
    mod = importlib.util.module_from_spec(spec)
    # faults.py uses relative imports only inside functions we don't call
    # (inject_write_errors / corrupt_checkpoint); corrupt_file is pure
    spec.loader.exec_module(mod)
    return mod


def read_out(path):
    resume, losses, sha = None, {}, None
    if not os.path.exists(path):
        return resume, losses, sha
    for line in open(path):
        parts = line.split()
        if not parts:
            continue
        if parts[0] == 'RESUME':
            resume = int(parts[1])
        elif parts[0] == 'DONE':
            sha = parts[1]
        elif parts[0].lstrip('-').isdigit():
            losses[int(parts[0])] = float(parts[1])
    return resume, losses, sha


def run_worker(ckpt_dir, out, total, k, every, kill_at=0, timeout=600):
    argv = [sys.executable, WORKER, ckpt_dir, out, str(total), str(k),
            str(every)]
    if kill_at:
        argv += [str(kill_at), '1']
    return subprocess.run(argv, capture_output=True, text=True,
                          timeout=timeout)


def corrupt_newest(ckpt_mod, faults, ckpt_dir, mode, rng):
    live = ckpt_mod.list_checkpoints(ckpt_dir)
    if not live:
        return None
    step, path = live[-1]
    if mode == 'random':
        mode = rng.choice(['shard', 'manifest', 'commit'])
    if mode == 'commit':
        try:
            os.remove(os.path.join(path, ckpt_mod._COMMIT))
        except FileNotFoundError:
            pass        # already damaged in an earlier round
    elif mode == 'manifest':
        faults.corrupt_file(os.path.join(path, ckpt_mod._MANIFEST),
                            mode='truncate')
    else:
        import json
        try:
            with open(os.path.join(path, ckpt_mod._MANIFEST)) as f:
                name = sorted(json.load(f)['files'])[0]
        except (OSError, ValueError, KeyError, IndexError):
            # manifest already rotted in an earlier round: hit any shard
            names = sorted(n for n in os.listdir(path)
                           if n not in (ckpt_mod._MANIFEST,
                                        ckpt_mod._COMMIT))
            if not names:
                return step, 'already-empty'
            name = names[0]
        faults.corrupt_file(os.path.join(path, name), mode='flip')
    return step, mode


# ---------------------------------------------------------------------------
# pod mode (ISSUE 10): kill ONE random host, restart the WHOLE pod
# ---------------------------------------------------------------------------
POD_WORKER = os.path.join(REPO, 'tests', 'pod_ft_worker.py')


def _free_port():
    import socket
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_pod(ckpt_dir, out_paths, total, every, kill_rank=None, kill_at=0,
            cache_dir=None, timeout=600, worker=None, data_file=None):
    """One pod incarnation: len(out_paths) worker processes joined through
    a fresh coordinator + run id. Returns [(returncode, stderr)] per
    rank; a process that outlives `timeout` (wedged survivor whose
    watchdog failed) is SIGKILLed — that is itself a detection failure
    the caller flags. With `data_file` the elastic worker contract is
    used (DATA_FILE argv slot, no MIN_POD_COMMITS — the victim waits for
    its exact boundary's POD_COMMIT)."""
    import uuid
    n = len(out_paths)
    port, run_id = _free_port(), uuid.uuid4().hex
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.pop('JAX_PLATFORMS', None)
        env.update({
            'PADDLE_TRAINERS': str(n),
            'PADDLE_TRAINER_ID': str(rank),
            'PADDLE_COORDINATOR': '127.0.0.1:%d' % port,
            'XLA_FLAGS': '--xla_force_host_platform_device_count=2',
            'PTPU_POD_RUN_ID': run_id,
            'PTPU_POD_HB_TIMEOUT': env_hb_timeout(),
        })
        if cache_dir:
            env['PTPU_COMPILE_CACHE'] = '1'
            env['JAX_COMPILATION_CACHE_DIR'] = cache_dir
        argv = [sys.executable, worker or POD_WORKER, ckpt_dir]
        if data_file:
            argv.append(data_file)
        argv += [out_paths[rank], str(total), str(every)]
        if kill_rank == rank:
            argv += [str(kill_at)] if data_file else [str(kill_at), '1']
        procs.append(subprocess.Popen(argv, env=env, cwd=REPO,
                                      stdout=subprocess.DEVNULL,
                                      stderr=subprocess.PIPE, text=True))
    results = []
    deadline = time.time() + timeout
    for p in procs:
        try:
            _out, err = p.communicate(timeout=max(5.0,
                                                  deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            _out, err = p.communicate()
            err += '\n[chaos] WEDGED: survivor never detected the dead ' \
                   'host within %ds' % timeout
        results.append((p.returncode, err))
    return results


def env_hb_timeout():
    # 8s default: tight enough for bounded detection, loose enough that
    # a loaded 2-core CI host compiling several pods at once cannot
    # starve a live worker's heartbeat thread into a false positive
    return os.environ.get('PTPU_POD_HB_TIMEOUT', '8')


def corrupt_newest_pod(ckpt_mod, faults, ckpt_dir, mode, rng):
    """Damage the newest POD checkpoint the way a crash/bit-rot would:
    'commit' removes the pod-level POD_COMMIT record, 'manifest'
    truncates a random host's manifest, 'shard' flips a byte in a random
    host's shard file."""
    live = ckpt_mod.list_checkpoints(ckpt_dir)
    if not live:
        return None
    step, path = live[-1]
    if mode == 'random':
        mode = rng.choice(['shard', 'manifest', 'commit'])
    if mode == 'commit':
        try:
            os.remove(os.path.join(path, ckpt_mod._POD_COMMIT))
        except FileNotFoundError:
            pass
        return step, 'commit'
    hosts = sorted(n for n in os.listdir(path)
                   if n.startswith(ckpt_mod._HOST_PREFIX)
                   and os.path.isdir(os.path.join(path, n)))
    if not hosts:
        return step, 'already-empty'
    host_dir = os.path.join(path, rng.choice(hosts))
    if mode == 'manifest':
        faults.corrupt_file(os.path.join(host_dir, ckpt_mod._MANIFEST),
                            mode='truncate')
        return step, 'manifest@%s' % os.path.basename(host_dir)
    import json
    try:
        with open(os.path.join(host_dir, ckpt_mod._MANIFEST)) as f:
            names = sorted(json.load(f)['files'])
    except (OSError, ValueError, KeyError):
        names = []
    names = names or sorted(n for n in os.listdir(host_dir)
                            if n not in (ckpt_mod._MANIFEST,
                                         ckpt_mod._COMMIT))
    if not names:
        return step, 'already-empty'
    faults.corrupt_file(os.path.join(host_dir, names[0]), mode='flip')
    return step, 'shard@%s' % os.path.basename(host_dir)


def pod_main(args, rng, ckpt_mod, faults, work, fail):
    n = args.pod
    ckpt_dir = os.path.join(work, 'pod-ckpts')
    cache_dir = os.path.join(work, 'compile-cache')
    outs = lambda tag: [os.path.join(work, '%s-r%d.txt' % (tag, r))  # noqa: E731,E501
                        for r in range(n)]

    ref_outs = outs('ref')
    t0 = time.time()
    res = run_pod(os.path.join(work, 'pod-ref-ckpts'), ref_outs,
                  args.total, args.every, cache_dir=cache_dir)
    if any(rc != 0 for rc, _ in res):
        return fail('pod reference run failed:\n%s'
                    % '\n'.join(err[-1500:] for _, err in res))
    refs = [read_out(p) for p in ref_outs]
    for r in range(1, n):
        if refs[r][1] != refs[0][1]:
            return fail('reference pod: replicated losses differ '
                        'between hosts 0 and %d' % r)
    print('[chaos] pod reference: %d hosts, %d steps, params %s  %.1fs'
          % (n, len(refs[0][1]), refs[0][2][:12], time.time() - t0))

    all_seen = {}
    for rnd in range(1, args.rounds + 1):
        victim = rng.randrange(n)
        kill_at = rng.randrange(args.every, args.total + args.every,
                                args.every)
        round_outs = outs('round-%d' % rnd)
        t0 = time.time()
        res = run_pod(ckpt_dir, round_outs, args.total, args.every,
                      kill_rank=victim, kill_at=kill_at,
                      cache_dir=cache_dir)
        if any('WEDGED' in err for _, err in res):
            return fail('round %d: a survivor never detected the dead '
                        'host (watchdog failure)' % rnd)
        outcome = []
        for r, (rc, err) in enumerate(res):
            if rc == 0:
                outcome.append('h%d:done' % r)
            elif r == victim and rc == -signal.SIGKILL:
                outcome.append('h%d:killed' % r)
            else:
                outcome.append('h%d:exit%s' % (r, rc))
        resume = read_out(round_outs[0])[0]
        for r in range(n):
            _resume, losses, _sha = read_out(round_outs[r])
            for idx, v in losses.items():
                if v != refs[r][1].get(idx):
                    return fail('round %d host %d: loss at step %d '
                                'diverged (%r vs %r)'
                                % (rnd, r, idx, v, refs[r][1].get(idx)))
                key = (r, idx)
                if key in all_seen and all_seen[key] != v:
                    return fail('round %d host %d: step %d not '
                                'reproducible across incarnations'
                                % (rnd, r, idx))
                all_seen[key] = v
        note = ''
        hit = None
        if args.corrupt != 'none':
            hit = corrupt_newest_pod(ckpt_mod, faults, ckpt_dir,
                                     args.corrupt, rng)
            if hit:
                note = ' corrupt[%s@ckpt-%d]' % (hit[1], hit[0])
        print('[chaos] pod round %d: resume=%s victim=h%d kill_at=%d %s '
              '%.1fs%s' % (rnd, resume, victim, kill_at,
                           ' '.join(outcome), time.time() - t0, note))

    fin_outs = outs('final')
    t0 = time.time()
    res = run_pod(ckpt_dir, fin_outs, args.total, args.every,
                  cache_dir=cache_dir)
    if any(rc != 0 for rc, _ in res):
        return fail('pod final run failed:\n%s'
                    % '\n'.join(err[-1500:] for _, err in res))
    for r in range(n):
        resume, losses, sha = read_out(fin_outs[r])
        for idx, v in losses.items():
            if v != refs[r][1].get(idx):
                return fail('pod final host %d: loss at step %d diverged'
                            % (r, idx))
        if sha != refs[r][2]:
            return fail('pod final host %d: params digest %s != '
                        'reference %s' % (r, sha, refs[r][2]))
    print('[chaos] pod final: resume=%s -> %d steps, params match the '
          'reference on every host  %.1fs'
          % (read_out(fin_outs[0])[0], args.total, time.time() - t0))
    print('[chaos] OK: pod of %d hosts survived %d kill-one-host rounds '
          '+ %s corruption, bit parity held on every host'
          % (n, args.rounds, args.corrupt))
    return 0


# ---------------------------------------------------------------------------
# resize mode (ISSUE 14): kill the pod at a COMMITTED boundary, relaunch
# on a randomly chosen DIFFERENT host count (elastic worker: sharded
# data journal, restore reshards to the new mesh, journal re-strides)
# ---------------------------------------------------------------------------
ELASTIC_WORKER = os.path.join(REPO, 'tests', 'elastic_pod_worker.py')
GLOBAL_BS = 16        # elastic worker contract (elastic_pod_worker.py)
RESIZE_LOSS_ATOL = 2e-3
RESIZE_LOSS_RTOL = 1e-3


def read_elastic_out(path):
    """Parse one elastic worker out file -> dict with resume, topo,
    reshard, restride, losses {step: float}, recs {step: [hash, ...]},
    sha."""
    out = {'resume': None, 'topo': None, 'reshard': None,
           'restride': None, 'losses': {}, 'recs': {}, 'sha': None,
           'stall': None}
    if not os.path.exists(path):
        return out
    for line in open(path):
        parts = line.split()
        if not parts:
            continue
        if parts[0] == 'RESUME':
            out['resume'] = int(parts[1])
        elif parts[0] == 'TOPO':
            out['topo'] = (int(parts[1]), int(parts[2]))
        elif parts[0] == 'RESHARD':
            out['reshard'] = (int(parts[1]), int(parts[2]),
                              float(parts[3]), float(parts[4]))
        elif parts[0] == 'RESTRIDE':
            out['restride'] = tuple(int(x) for x in parts[1:4])
        elif parts[0] == 'RECS':
            out['recs'][int(parts[1])] = parts[2].split(',')
        elif parts[0] == 'STALL':
            out['stall'] = float(parts[1])
        elif parts[0] == 'DONE':
            out['sha'] = parts[1]
        elif parts[0].lstrip('-').isdigit():
            out['losses'][int(parts[0])] = float(parts[1])
    return out


def merge_pod_recs(host_outs, fail):
    """{step: sorted record hashes across all hosts}; a duplicate hash
    within one step means two hosts trained the same chunk — an
    exactly-once violation caught immediately."""
    merged = {}
    for r, o in enumerate(host_outs):
        for s, hs in o['recs'].items():
            merged.setdefault(s, []).extend(hs)
    for s, hs in merged.items():
        if len(hs) != len(set(hs)):
            return fail('step %d trained a chunk twice across hosts '
                        '(exactly-once violation)' % s), None
    return None, {s: sorted(hs) for s, hs in merged.items()}


def check_resize_round(refs_losses, ref_recs, killed, resumed, resume_at,
                       total, dataset_hashes, fail, label):
    """The resize acceptance: loss-trajectory parity within
    float-accumulation tolerance, identical per-step record SETS, and
    exactly-once epoch digests over the effective history (killed run
    before the resume point, resumed run after)."""
    err, killed_recs = merge_pod_recs(killed, fail)
    if err is not None:
        return err
    err, resumed_recs = merge_pod_recs(resumed, fail)
    if err is not None:
        return err
    for tag, outs in (('killed', killed), ('resumed', resumed)):
        for r, o in enumerate(outs):
            for s, v in o['losses'].items():
                ref = refs_losses.get(s)
                if ref is None:
                    return fail('%s %s host %d trained unexpected step %d'
                                % (label, tag, r, s))
                if abs(v - ref) > RESIZE_LOSS_ATOL \
                        + RESIZE_LOSS_RTOL * abs(ref):
                    return fail(
                        '%s %s host %d: loss at step %d outside the '
                        'float-accumulation tolerance (%r vs ref %r)'
                        % (label, tag, r, s, v, ref))
    effective = {}
    for s in range(total):
        src = killed_recs if s < resume_at else resumed_recs
        if s not in src:
            return fail('%s: no record accounting for step %d (%s arm)'
                        % (label, s, 'killed' if s < resume_at
                           else 'resumed'))
        effective[s] = src[s]
        if ref_recs.get(s) is not None \
                and sorted(ref_recs[s]) != sorted(src[s]):
            return fail('%s: step %d trained a different record SET '
                        'than the reference (data-plane stride drift)'
                        % (label, s))
        if len(src[s]) != GLOBAL_BS:
            return fail('%s: step %d trained %d records, want %d'
                        % (label, s, len(src[s]), GLOBAL_BS))
    steps_per_epoch = len(dataset_hashes) // GLOBAL_BS
    for e in range(total // steps_per_epoch):
        got = []
        for s in range(e * steps_per_epoch, (e + 1) * steps_per_epoch):
            got.extend(effective[s])
        if sorted(got) != sorted(dataset_hashes):
            return fail('%s: epoch %d digest is not exactly-once '
                        '(%d records trained, %d unique, dataset %d)'
                        % (label, e, len(got), len(set(got)),
                           len(dataset_hashes)))
    return None


def resize_main(args, rng, work, fail):
    """Elastic chaos: reference at --pod N, then per round kill a fresh
    pod at a committed boundary and relaunch on a DIFFERENT host count,
    asserting loss parity within tolerance + exactly-once digests."""
    n0 = args.pod
    counts = sorted({int(c) for c in args.resize_counts.split(',')})
    for c in counts + [n0]:
        if GLOBAL_BS % c:
            return fail('host count %d does not divide the global '
                        'batch %d' % (c, GLOBAL_BS))
    # fail these BEFORE the minutes-long reference run: every round
    # needs a host count different from the current one (rounds chain,
    # so a 1-entry pool only survives round 1), and a kill boundary
    # strictly INSIDE the run
    if not [c for c in counts if c != n0] \
            or (args.rounds > 1 and len(counts) < 2):
        return fail('--resize-counts %r cannot supply a DIFFERENT host '
                    'count for every one of %d round(s) starting from '
                    '--pod %d' % (args.resize_counts, args.rounds, n0))
    if args.total <= args.every:
        return fail('--resize needs --total (%d) > --every (%d): the '
                    'kill must land on a committed boundary strictly '
                    'inside the run so the relaunch has steps left'
                    % (args.total, args.every))
    cache_dir = os.path.join(work, 'compile-cache')
    data = os.path.join(work, 'elastic-data.rio')
    num_records = GLOBAL_BS * 4            # 4 steps per epoch
    r = subprocess.run([sys.executable, ELASTIC_WORKER, '--make-data',
                        data, str(num_records)], capture_output=True,
                       text=True, cwd=REPO, timeout=240)
    if r.returncode != 0:
        return fail('dataset build failed:\n%s' % r.stderr[-1500:])
    dataset_hashes = [l.strip() for l in open(data + '.hashes')
                      if l.strip()]
    outs = lambda tag, n: [os.path.join(work, '%s-r%d.txt' % (tag, r))  # noqa: E731,E501
                           for r in range(n)]

    t0 = time.time()
    ref_outs = outs('ref', n0)
    res = run_pod(os.path.join(work, 'ref-ckpts'), ref_outs, args.total,
                  args.every, cache_dir=cache_dir, worker=ELASTIC_WORKER,
                  data_file=data)
    if any(rc != 0 for rc, _ in res):
        return fail('elastic reference run failed:\n%s'
                    % '\n'.join(err[-1500:] for _, err in res))
    refs = [read_elastic_out(p) for p in ref_outs]
    for r_ in range(1, n0):
        if refs[r_]['losses'] != refs[0]['losses']:
            return fail('reference pod: replicated losses differ '
                        'between hosts 0 and %d' % r_)
    err, ref_recs = merge_pod_recs(refs, fail)
    if err is not None:
        return err
    print('[chaos] resize reference: %d hosts, %d steps, %d records/'
          'epoch  %.1fs' % (n0, len(refs[0]['losses']), num_records,
                            time.time() - t0))

    cur_n = n0
    for rnd in range(1, args.rounds + 1):
        ckpt = os.path.join(work, 'resize-ckpts-%d' % rnd)
        victim = rng.randrange(cur_n)
        # a committed boundary strictly inside the run, so the relaunch
        # has steps left to train
        kill_at = rng.randrange(args.every, args.total, args.every)
        new_n = rng.choice([c for c in counts if c != cur_n])
        t0 = time.time()
        res = run_pod(ckpt, outs('rz%d-kill' % rnd, cur_n), args.total,
                      args.every, kill_rank=victim, kill_at=kill_at,
                      cache_dir=cache_dir, worker=ELASTIC_WORKER,
                      data_file=data)
        if any('WEDGED' in err for _, err in res):
            return fail('round %d: a survivor never detected the dead '
                        'host' % rnd)
        if res[victim][0] != -signal.SIGKILL:
            return fail('round %d: victim exited %s, expected SIGKILL'
                        % (rnd, res[victim][0]))
        killed = [read_elastic_out(p) for p in outs('rz%d-kill' % rnd,
                                                    cur_n)]
        res = run_pod(ckpt, outs('rz%d-new' % rnd, new_n), args.total,
                      args.every, cache_dir=cache_dir,
                      worker=ELASTIC_WORKER, data_file=data)
        if any(rc != 0 for rc, _ in res):
            return fail('round %d: resized relaunch (%d->%d hosts) '
                        'failed:\n%s' % (rnd, cur_n, new_n,
                                         '\n'.join(err[-1500:]
                                                   for _, err in res)))
        resumed = [read_elastic_out(p) for p in outs('rz%d-new' % rnd,
                                                     new_n)]
        # the resume point is the newest COMMITTED boundary <= kill_at
        # (a boundary a busy writer declined commits nothing); every
        # resumed host must agree on it and it must exist at all
        resume_at = resumed[0]['resume']
        for r_, o in enumerate(resumed):
            if o['resume'] != resume_at or not resume_at \
                    or resume_at > kill_at or resume_at % args.every:
                return fail('round %d host %d resumed at %s, expected '
                            'one committed boundary <= %d on every host'
                            % (rnd, r_, o['resume'], kill_at))
            if o['topo'] != (cur_n, new_n):
                return fail('round %d host %d topo %r, expected (%d, %d)'
                            % (rnd, r_, o['topo'], cur_n, new_n))
            if o['reshard'] is None or o['reshard'][0] < 1:
                return fail('round %d host %d: resize did not engage '
                            'the resharding path (%r)'
                            % (rnd, r_, o['reshard']))
        err = check_resize_round(
            refs[0]['losses'], ref_recs, killed, resumed, resume_at,
            args.total, dataset_hashes, fail, 'round %d' % rnd)
        if err is not None:
            return err
        print('[chaos] resize round %d: %d hosts killed@%d (victim h%d) '
              '-> resumed on %d hosts at committed step %d, loss parity '
              'within tolerance, epochs exactly-once  %.1fs'
              % (rnd, cur_n, kill_at, victim, new_n, resume_at,
                 time.time() - t0))
        cur_n = new_n
    print('[chaos] OK: %d resize rounds over host counts %r, loss '
          'parity within tolerance + exactly-once epoch digests held'
          % (args.rounds, counts))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description='kill/corrupt/restart chaos loop over the checkpoint '
                    'subsystem; exit 0 on bit parity with an '
                    'uninterrupted run')
    ap.add_argument('--rounds', type=int, default=3,
                    help='kill rounds before the final full run')
    ap.add_argument('--total', type=int, default=24)
    ap.add_argument('--k', type=int, default=4,
                    help='steps per dispatch (kills land on multiples)')
    ap.add_argument('--every', type=int, default=4,
                    help='checkpoint_every steps')
    ap.add_argument('--corrupt', default='none',
                    choices=['none', 'shard', 'manifest', 'commit',
                             'random'],
                    help='damage the newest checkpoint after each kill')
    ap.add_argument('--seed', type=int, default=None)
    ap.add_argument('--workdir', default=None)
    ap.add_argument('--keep', action='store_true',
                    help='keep the workdir for inspection')
    ap.add_argument('--pod', type=int, default=0, metavar='N',
                    help='pod mode: N >= 2 composed-mesh processes; each '
                         'round SIGKILLs ONE random host mid-step and '
                         'restarts the whole pod (sharded two-phase '
                         'checkpoints, heartbeat watchdog, warm compile '
                         'cache)')
    ap.add_argument('--resize', action='store_true',
                    help='elastic mode (with --pod N): each round kills '
                         'the pod at a COMMITTED boundary and relaunches '
                         'on a randomly chosen DIFFERENT host count '
                         '(topology-change restore + journal re-stride); '
                         'asserts loss parity within float-accumulation '
                         'tolerance and exactly-once epoch digests')
    ap.add_argument('--resize-counts', default='1,2,4', metavar='A,B,..',
                    help='host-count pool --resize draws from '
                         '(default 1,2,4)')
    args = ap.parse_args(argv)

    seed = args.seed if args.seed is not None else int(time.time())
    rng = random.Random(seed)
    ckpt_mod = _checkpoint_mod()
    faults = _faults_mod()
    work = args.workdir or tempfile.mkdtemp(prefix='ptpu-chaos-')
    os.makedirs(work, exist_ok=True)
    ckpt_dir = os.path.join(work, 'ckpts')
    print('[chaos] workdir=%s seed=%d rounds=%d total=%d k=%d every=%d '
          'corrupt=%s' % (work, seed, args.rounds, args.total, args.k,
                          args.every, args.corrupt))

    def fail(msg):
        print('[chaos] FAIL: %s' % msg)
        print('[chaos] workdir kept at %s' % work)
        return 1

    if args.resize:
        if args.pod < 2:
            ap.error('--resize needs --pod N (N >= 2) for the initial '
                     'topology')
        rc = resize_main(args, rng, work, fail)
        if rc == 0 and not args.keep and args.workdir is None:
            shutil.rmtree(work, ignore_errors=True)
        return rc

    if args.pod:
        if args.pod < 2:
            ap.error('--pod needs at least 2 hosts')
        rc = pod_main(args, rng, ckpt_mod, faults, work, fail)
        if rc == 0 and not args.keep and args.workdir is None:
            shutil.rmtree(work, ignore_errors=True)
        return rc

    ref_out = os.path.join(work, 'ref.txt')
    r = run_worker('-', ref_out, args.total, args.k, args.every)
    if r.returncode != 0:
        return fail('reference run failed:\n%s' % r.stderr[-2000:])
    _, ref_losses, ref_sha = read_out(ref_out)
    print('[chaos] reference: %d steps, params %s' % (len(ref_losses),
                                                      ref_sha[:12]))

    all_seen = {}
    for rnd in range(1, args.rounds + 1):
        kill_at = rng.randrange(args.k, args.total + args.k, args.k)
        out = os.path.join(work, 'round-%d.txt' % rnd)
        t0 = time.time()
        r = run_worker(ckpt_dir, out, args.total, args.k, args.every,
                       kill_at=kill_at)
        resume, losses, sha = read_out(out)
        if r.returncode == 0 and sha is not None:
            outcome = 'completed'
        elif r.returncode == -signal.SIGKILL:
            outcome = 'killed@%d' % max(losses, default=-1)
        else:
            return fail('round %d crashed (rc=%s):\n%s'
                        % (rnd, r.returncode, r.stderr[-2000:]))
        for idx, v in losses.items():
            if v != ref_losses.get(idx):
                return fail('round %d: loss at step %d diverged '
                            '(%r vs %r)' % (rnd, idx, v,
                                            ref_losses.get(idx)))
            if idx in all_seen and all_seen[idx] != v:
                return fail('round %d: step %d not reproducible across '
                            'incarnations' % (rnd, idx))
        all_seen.update(losses)
        note = ''
        if args.corrupt != 'none' and r.returncode != 0:
            hit = corrupt_newest(ckpt_mod, faults, ckpt_dir, args.corrupt,
                                 rng)
            if hit:
                note = ' corrupt[%s@ckpt-%d]' % (hit[1], hit[0])
        print('[chaos] round %d: resume=%s kill_at=%d %s steps_ok=%d '
              '%.1fs%s' % (rnd, resume, kill_at, outcome, len(losses),
                           time.time() - t0, note))

    out = os.path.join(work, 'final.txt')
    r = run_worker(ckpt_dir, out, args.total, args.k, args.every)
    if r.returncode != 0:
        return fail('final run failed:\n%s' % r.stderr[-2000:])
    resume, losses, sha = read_out(out)
    for idx, v in losses.items():
        if v != ref_losses.get(idx):
            return fail('final: loss at step %d diverged' % idx)
    if sha != ref_sha:
        return fail('final params digest %s != reference %s'
                    % (sha, ref_sha))
    print('[chaos] final: resume=%s -> %d steps, params %s == reference'
          % (resume, args.total, sha[:12]))
    print('[chaos] OK: %d kill rounds + %s corruption, bit parity held'
          % (args.rounds, args.corrupt))
    if not args.keep and args.workdir is None:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
