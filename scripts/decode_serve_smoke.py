#!/usr/bin/env python
"""Smoke the continuous-decode serving tier (ISSUE 8 CI satellite;
block-paged tier bars added by ISSUE 13): build a tiny decoder LM,
export the two-program paged-KV artifact, then A/B a Poisson arrival
stream through DecodingPredictor's in-flight batching against strictly
sequential (one-request-at-a-time) decode.

    python scripts/decode_serve_smoke.py

Asserts, on the CPU dispatch-floor proxy:
  * per-request transcripts BIT-IDENTICAL between the two arms (and a
    fresh framework-free subprocess reproduces them with 0 XLA compiles
    — the warm-start bar);
  * continuous batching >= 3x sequential tokens/s under the Poisson
    load (fixed [max_slots] step cost amortizes across co-resident
    requests exactly like the batch dispatch floor);
  * measured p50/p99 time-to-first-token reported for the Poisson arm.

Block-paged tier (ISSUE 13):
  * prefix-share A/B: a shared-system-prompt workload vs the same
    workload with unique prefixes — peak cache blocks (= cache HBM)
    must drop >= 1.5x (the effective-slot-capacity multiplier at fixed
    cache bytes), transcripts bit-identical to the no-sharing serve;
  * beam reorder measured BLOCK-level: copy-on-write dispatch bytes
    must undercut a whole-state reorder gather >= 10x.
Exits non-zero on any failed bar.
"""
import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.inference import (DecodingPredictor,  # noqa: E402
                                  export_decode)

# enough total work that each arm runs ~a second on the CPU proxy —
# with tiny configs the arms finish in tens of ms and scheduler noise
# swamps the capacity ratio the bar is about. Vocab is large enough
# that a random-init greedy decoder rarely emits eos immediately:
# prefill is serial per request in BOTH arms, so a fleet of 1-token
# requests would cap the achievable step-sharing speedup well below
# the bar regardless of scheduling.
VOCAB, SLOTS = 251, 8
MAX_NEW = int(os.environ.get('PTPU_DECODE_SMOKE_MAX_NEW', '24'))
N_REQ = int(os.environ.get('PTPU_DECODE_SMOKE_REQS', '96'))


def _export(art_dir, **kw):
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        cfg = dict(vocab=VOCAB, d_model=16, n_head=2, n_layer=2,
                   d_ff=32, max_slots=SLOTS, max_cache_len=48,
                   chunk_sizes=(4, 8), block_size=16, eos_id=1)
        cfg.update(kw)
        spec = build_decode_spec(**cfg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(spec['startup'])
        export_decode(spec, art_dir, scope=scope)


def _prompts(n):
    rng = np.random.RandomState(5)
    return [rng.randint(2, VOCAB, int(rng.randint(2, 9))) for _ in range(n)]


def _consume(stream, stamps):
    for _ in stream:
        stamps.append(time.perf_counter())


def _prefix_share_ab(d):
    """ISSUE 13 part B: shared-system-prompt workload vs the same
    workload with unique prefixes, on one block-paged artifact. Returns
    the result dict; raises AssertionError on a failed bar."""
    art = os.path.join(d, 'block_art')
    _export(art, max_cache_len=64, block_size=8, chunk_sizes=(8, 16))
    rng = np.random.RandomState(9)
    system = rng.randint(2, VOCAB, 32)           # 4 full blocks
    n = 16
    suffixes = [rng.randint(2, VOCAB, 6) for _ in range(n)]
    shared = [np.concatenate([system, s]) for s in suffixes]
    unique = [np.concatenate([rng.randint(2, VOCAB, 32), s])
              for s in suffixes]

    def run(prompts, no_share=False):
        pred = DecodingPredictor(art)
        try:
            pred.warmup()
            if no_share:
                out = []
                for p in prompts:
                    pred.block_manager.evict_all_prefixes()
                    out.append(pred.generate(p, max_new_tokens=12))
                pred.block_manager.evict_all_prefixes()
                return out, pred.stats.snapshot()
            # let the first request finish prefill (publishing the
            # prefix) before the rest arrive: the A/B measures steady-
            # state sharing, not the cold first wave
            first = pred.submit(prompts[0], max_new_tokens=12)
            next(iter(first))
            rest = [pred.submit(p, max_new_tokens=12)
                    for p in prompts[1:]]
            out = [first.result(300)] + [s.result(300) for s in rest]
            return out, pred.stats.snapshot()
        finally:
            pred.close()

    truth, _ = run(shared, no_share=True)        # sharing disabled
    got_shared, snap_s = run(shared)
    _, snap_u = run(unique)
    assert got_shared == truth, \
        'prefix sharing changed transcripts'
    assert snap_s['prefix_hits'] >= n - 2, snap_s['prefix_hits']
    cap_x = snap_u['blocks_peak'] / float(snap_s['blocks_peak'])
    # bytes per block: block_size rows x d_model, K+V per layer, f32
    blk_bytes = 8 * 16 * 4 * (2 * 2)
    print('prefix share: peak blocks %d (unique) -> %d (shared) = '
          '%.2fx effective capacity at fixed cache HBM '
          '(%.1f -> %.1f KiB), %d hits, %d prompt tokens reused'
          % (snap_u['blocks_peak'], snap_s['blocks_peak'], cap_x,
             snap_u['blocks_peak'] * blk_bytes / 1024.0,
             snap_s['blocks_peak'] * blk_bytes / 1024.0,
             snap_s['prefix_hits'], snap_s['prefix_tokens_reused']))
    assert cap_x >= 1.5, \
        'prefix sharing bought only %.2fx effective capacity' % cap_x

    # -- beam reorder, measured block-level --------------------------------
    pred = DecodingPredictor(art)
    try:
        pred.warmup()
        beams = [pred.submit(p, max_new_tokens=12, beam=4)
                 for p in shared[:4]]
        for s in beams:
            s.result(300)
        bsnap = pred.stats.snapshot()
    finally:
        pred.close()
    # a reorder by whole-slot-row gather would move the WHOLE cache state
    # (S rows x max_cache_len x d_model, K+V per layer); the block tier
    # dispatches only the diverged blocks' copy pairs
    slot_bytes = bsnap['reorders'] * SLOTS * 64 * 16 * 4 * (2 * 2)
    cow_bytes = bsnap['cow_blocks'] * blk_bytes
    ratio = slot_bytes / max(cow_bytes, 1)
    print('beam reorder: %d reorders -> %d CoW blocks in %d copy '
          'dispatches; %.1f KiB slot-gather equivalent vs %.1f KiB '
          'block copies (%.0fx less dispatched)'
          % (bsnap['reorders'], bsnap['cow_blocks'],
             bsnap['blockcopies'], slot_bytes / 1024.0,
             cow_bytes / 1024.0, ratio))
    assert bsnap['cow_blocks'] > 0
    assert ratio >= 10.0, \
        'block-level reorder saved only %.1fx dispatch bytes' % ratio
    return {'capacity_x': round(cap_x, 2),
            'peak_blocks_shared': snap_s['blocks_peak'],
            'peak_blocks_unique': snap_u['blocks_peak'],
            'prefix_hits': snap_s['prefix_hits'],
            'reorder_bytes_x': round(ratio, 1)}


def main():
    with tempfile.TemporaryDirectory() as d:
        art = os.path.join(d, 'decode_art')
        _export(art)
        prompts = _prompts(N_REQ)
        pred = DecodingPredictor(art)
        try:
            pred.warmup()
            # -- sequential arm: one request at a time -------------------
            t0 = time.perf_counter()
            seq = [pred.generate(p, max_new_tokens=MAX_NEW)
                   for p in prompts]
            seq_s = time.perf_counter() - t0
            seq_tokens = sum(len(t) for t in seq)
            seq_tok_s = seq_tokens / seq_s
            seq_steps = pred.stats.snapshot()['steps']
            pred.stats.reset()
            # -- continuous arm: Poisson arrivals offered ABOVE the
            # MEASURED sequential request rate (early-eos sequences make
            # requests much cheaper than MAX_NEW tokens, so a token-
            # derived rate would under-offer and idle the slots). The
            # backlog keeps every slot occupied — the regime continuous
            # batching exists for; shedding off so every transcript
            # completes for the A/B.
            rate = float(os.environ.get('PTPU_DECODE_SMOKE_RATE_X', '8')) \
                * (N_REQ / seq_s)
            arrivals = np.cumsum(np.random.RandomState(1).exponential(
                1.0 / rate, N_REQ))
            streams = []
            t0 = time.perf_counter()
            for i, p in enumerate(prompts):
                delay = t0 + arrivals[i] - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                streams.append(pred.submit(p, max_new_tokens=MAX_NEW))
            con = [s.result(300) for s in streams]
            con_s = time.perf_counter() - t0
            snap = pred.stats.snapshot()
        finally:
            pred.close()
        con_tok_s = sum(len(t) for t in con) / con_s
        speedup = con_tok_s / seq_tok_s
        print('sequential: %7.1f tok/s  (%d requests, %d tokens, %d steps '
              'of %d slots)' % (seq_tok_s, N_REQ, seq_tokens, seq_steps,
                                SLOTS))
        print('continuous: %7.1f tok/s  (%d steps, occupancy %.2f, '
              'offered %.1f req/s)' % (con_tok_s, snap['steps'],
                                       snap['occupancy'], rate))
        print('ttft ms: p50=%.2f p99=%.2f   itl ms: p50=%.2f p99=%.2f' %
              (snap['ttft_p50_ms'], snap['ttft_p99_ms'],
               snap['itl_p50_ms'], snap['itl_p99_ms']))
        print(json.dumps({'seq_tok_s': round(seq_tok_s, 1),
                          'con_tok_s': round(con_tok_s, 1),
                          'speedup': round(speedup, 2),
                          'occupancy': snap['occupancy'],
                          'ttft_p50_ms': snap['ttft_p50_ms'],
                          'ttft_p99_ms': snap['ttft_p99_ms']}))
        if con != seq:
            print('FAIL: continuous transcripts diverge from sequential',
                  file=sys.stderr)
            return 1
        if speedup < 3.0:
            print('FAIL: continuous batching %.2fx < 3x sequential '
                  'tokens/s' % speedup, file=sys.stderr)
            return 1
        # -- warm fresh-process arm: 0 compiles, same bits ---------------
        worker = os.path.join(REPO, 'tests', 'decode_serve_worker.py')
        r = subprocess.run(
            [sys.executable, worker, art, '23', '4', str(MAX_NEW)],
            capture_output=True, text=True, timeout=600)
        if r.returncode != 0 or 'DECODE_OK' not in r.stdout:
            sys.stderr.write(r.stdout + r.stderr)
            print('FAIL: warm decode worker failed', file=sys.stderr)
            return 1
        payload = json.loads(
            [l for l in r.stdout.splitlines()
             if l.startswith('DECODE ')][0][len('DECODE '):])
        if payload['compiles'] != 0:
            print('FAIL: warm fresh process performed %d XLA compiles '
                  '(want 0)' % payload['compiles'], file=sys.stderr)
            return 1
        rng = np.random.RandomState(23)
        warm_prompts = [rng.randint(2, VOCAB, rng.randint(2, 9))
                        for _ in range(4)]
        pred = DecodingPredictor(art)
        try:
            want = [pred.generate(p, max_new_tokens=MAX_NEW)
                    for p in warm_prompts]
        finally:
            pred.close()
        if payload['greedy'] != want:
            print('FAIL: warm-process transcripts diverge', file=sys.stderr)
            return 1
        # -- ISSUE 13: block-paged tier bars -----------------------------
        try:
            share = _prefix_share_ab(d)
        except AssertionError as e:
            print('FAIL: %s' % e, file=sys.stderr)
            return 1
        print(json.dumps(share))
        print('decode smoke OK: %.2fx tokens/s, bit-identical '
              'transcripts, 0 warm compiles; prefix share %.2fx '
              'capacity, reorder bytes %.0fx down'
              % (speedup, share['capacity_x'], share['reorder_bytes_x']))
    return 0


if __name__ == '__main__':
    sys.exit(main())
