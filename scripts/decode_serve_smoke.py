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
    must undercut the slot tier's whole-state reorder gathers >= 10x;
  * chunked prefill: while a max-length prompt admits, the running
    streams' worst inter-token gap must stay >= 2x below the measured
    stall the slot tier's monolithic prefill inflicts, with the long
    prompt's transcript bit-identical across both tiers.
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
                   prompt_buckets=(4, 8), eos_id=1)
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
    _export(art, max_cache_len=64, block_size=8, prompt_buckets=(8, 16))
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
    # one slot-layout reorder gathers the WHOLE cache state (S rows x
    # max_cache_len x d_model, K+V per layer); the block tier dispatches
    # only the diverged blocks' copy pairs
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


def _chunked_prefill_itl(d):
    """ISSUE 13 part C: p99 ITL of running streams while a max-length
    prompt admits — chunked prefill (block tier) vs the monolithic
    prefill stall (slot tier). Returns the result dict; raises
    AssertionError on a failed bar."""
    import threading
    # big enough that the monolithic prefill stall is unmistakable on
    # the CPU proxy (a 1000-token causal prefill at d_model 128), small
    # enough to export in seconds
    cfg = dict(d_model=128, n_head=8, n_layer=2, d_ff=256, max_slots=4,
               max_cache_len=1088)
    slot_art = os.path.join(d, 'itl_slot')
    blk_art = os.path.join(d, 'itl_block')
    _export(slot_art, prompt_buckets=(8, 1024), **cfg)
    _export(blk_art, prompt_buckets=(8, 32), block_size=32, **cfg)
    rng = np.random.RandomState(11)
    bg_prompts = [rng.randint(2, VOCAB, 6) for _ in range(3)]
    long_prompt = rng.randint(2, VOCAB, 1000)

    def trial(art):
        pred = DecodingPredictor(art)
        try:
            pred.warmup()
            stamps = [[] for _ in bg_prompts]
            threads = []
            bgs = []
            for p, ts in zip(bg_prompts, stamps):
                s = pred.submit(p, max_new_tokens=160)
                bgs.append(s)
                t = threading.Thread(target=_consume, args=(s, ts),
                                     daemon=True)
                t.start()
                threads.append(t)
            while any(len(ts) < 12 for ts in stamps):
                time.sleep(0.005)
            t_admit = time.perf_counter()
            long_s = pred.submit(long_prompt, max_new_tokens=8)
            long_out = long_s.result(600)
            t_done = time.perf_counter()
            for t in threads:
                t.join(300)
            base, stall = [], 0.0
            for ts in stamps:
                gaps = np.diff([t for t in ts if t <= t_admit])
                base.extend(gaps.tolist())
                w = [t for t in ts if t_admit - 0.05 <= t <= t_done]
                if len(w) >= 2:
                    stall = max(stall, float(np.max(np.diff(w))))
                # a stream that emitted NOTHING across the window
                # stalled for the whole admission
                inside = [t for t in ts if t_admit <= t <= t_done]
                if not inside and ts and ts[-1] > t_done:
                    stall = max(stall, t_done - t_admit)
            return (long_out, float(np.percentile(base, 99)) * 1e3,
                    stall * 1e3)
        finally:
            pred.close()

    def run(art, trials=3):
        # the stall statistic is a one-shot MAX gap: scheduler jitter,
        # GC, or a slow consumer wakeup can only inflate it, never
        # shrink it — so the MIN across trials is the tightest estimate
        # of the true admission stall (and what the 2x bar compares)
        outs, bases, stalls = [], [], []
        for _ in range(trials):
            o, b, s = trial(art)
            outs.append(o)
            bases.append(b)
            stalls.append(s)
        assert all(o == outs[0] for o in outs[1:]), \
            'long-prompt transcript varied across trials'
        return outs[0], float(np.median(bases)), float(min(stalls))

    long_slot, base_slot, stall_slot = run(slot_art)
    long_blk, base_blk, stall_blk = run(blk_art)
    assert long_slot == long_blk, \
        'chunked prefill changed the long prompt transcript'
    print('chunked prefill: worst running-stream gap while a %d-token '
          'prompt admits: slot %.1f ms (baseline itl p99 %.1f) vs '
          'block %.1f ms (baseline %.1f)'
          % (len(long_prompt), stall_slot, base_slot, stall_blk,
             base_blk))
    assert stall_slot >= 2.0 * stall_blk, \
        'monolithic prefill stall %.1f ms not >= 2x chunked %.1f ms' \
        % (stall_slot, stall_blk)
    return {'stall_slot_ms': round(stall_slot, 1),
            'stall_block_ms': round(stall_blk, 1),
            'itl_p99_base_ms': round(base_blk, 1)}


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
            itl = _chunked_prefill_itl(d)
        except AssertionError as e:
            print('FAIL: %s' % e, file=sys.stderr)
            return 1
        print(json.dumps(dict(share, **itl)))
        print('decode smoke OK: %.2fx tokens/s, bit-identical '
              'transcripts, 0 warm compiles; prefix share %.2fx '
              'capacity, reorder bytes %.0fx down, chunked-prefill '
              'stall %.1f -> %.1f ms'
              % (speedup, share['capacity_x'], share['reorder_bytes_x'],
                 itl['stall_slot_ms'], itl['stall_block_ms']))
    return 0


if __name__ == '__main__':
    sys.exit(main())
