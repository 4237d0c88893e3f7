"""Continuous in-flight decode serving (ISSUE 8): bit-identity of
continuously batched decode vs one-request-at-a-time decode (greedy and
fixed-width beam), slot free/reuse under staggered arrivals, deadline
expiry mid-decode, shedding, and fresh-subprocess warm start with zero
XLA compiles."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import (DecodingPredictor, export_decode,
                                  ServerOverloaded, DeadlineExceeded)

VOCAB, SLOTS, CACHE, CHUNKS, BLOCK = 37, 4, 64, (4, 8), 4


@pytest.fixture(scope='module')
def artifact(tmp_path_factory):
    """One tiny decoder-LM artifact per module: 2 layers, 4 slots,
    prefill chunks (4, 8), pages of 4 rows, AOT sidecars on (export
    default)."""
    from models.transformer import build_decode_spec
    out = str(tmp_path_factory.mktemp('decode') / 'art')
    main, startup = fluid.Program(), fluid.Program()
    prev_m = fluid.switch_main_program(main)
    prev_s = fluid.switch_startup_program(startup)
    scope = fluid.core.Scope()
    try:
        with fluid.scope_guard(scope):
            spec = build_decode_spec(
                vocab=VOCAB, d_model=16, n_head=2, n_layer=2, d_ff=32,
                max_slots=SLOTS, max_cache_len=CACHE,
                chunk_sizes=CHUNKS, block_size=BLOCK, eos_id=1)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(spec['startup'])
            export_decode(spec, out, scope=scope)
    finally:
        fluid.switch_main_program(prev_m)
        fluid.switch_startup_program(prev_s)
    return out


def _prompts(seed, n, lo=2, hi=None):
    rng = np.random.RandomState(seed)
    return [rng.randint(lo, hi or VOCAB, int(rng.randint(2, 9)))
            for _ in range(n)]


def test_artifact_layout(artifact):
    from paddle_tpu.inference import decoding
    with open(os.path.join(artifact, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    assert sig['kind'] == 'decode'
    assert sig['max_slots'] == SLOTS
    assert sig['layout'] == 'block'
    assert sig['chunk_buckets'] == sorted(CHUNKS)
    assert len(sig['state']) == 4  # 2 layers x K/V
    for e in sig['state']:
        assert e['shape'][:2] == [SLOTS * (CACHE // BLOCK) + 1, BLOCK]
    for d in ([decoding._STEP_DIR, decoding._ZEROS_DIR,
               decoding._BLOCKCOPY_DIR] +
              [decoding._CHUNK_DIR % c for c in CHUNKS]):
        assert os.path.exists(os.path.join(artifact, d, 'module.jaxexport'))
        # export-time AOT warm-start sidecar per program
        assert os.path.exists(os.path.join(artifact, d, 'aot_cpu.jaxexec'))


def test_greedy_bit_identity_continuous_vs_sequential(artifact):
    """12 requests over 4 slots: transcripts must be bit-identical to
    serving each request alone (row-independent slots, masked attention),
    and slots must recycle (more requests than slots all complete)."""
    prompts = _prompts(11, 12)
    with DecodingPredictor(artifact) as pred:
        seq = [pred.generate(p, max_new_tokens=10) for p in prompts]
        snap_seq = pred.stats.snapshot()
        assert snap_seq['requests'] == 12
        pred.stats.reset()
        streams = [pred.submit(p, max_new_tokens=10) for p in prompts]
        con = [s.result(120) for s in streams]
        snap = pred.stats.snapshot()
    assert con == seq
    assert snap['requests'] == 12 and snap['prefills'] == 12
    # continuous batching packs multiple requests per step
    assert snap['occupancy'] > snap_seq['occupancy']
    assert snap['steps'] < snap_seq['steps']


def test_greedy_bit_identity_staggered_arrivals(artifact):
    """Requests joining MID-decode (staggered arrivals) change nothing
    about earlier requests' streams."""
    prompts = _prompts(12, 6)
    with DecodingPredictor(artifact) as pred:
        seq = [pred.generate(p, max_new_tokens=12) for p in prompts]
        streams = []
        for p in prompts:
            streams.append(pred.submit(p, max_new_tokens=12))
            time.sleep(0.002)  # land inside the running batch
        con = [s.result(120) for s in streams]
    assert con == seq


def test_beam_bit_identity(artifact):
    """Fixed-width beam (3 slots per request) under co-residency with
    greedy traffic: hypotheses and scores bit-match solo runs."""
    prompts = _prompts(13, 4)
    with DecodingPredictor(artifact) as pred:
        solo = [pred.generate(p, max_new_tokens=8, beam=3) for p in prompts]
        beams = [pred.submit(p, max_new_tokens=8, beam=3)
                 for p in prompts[:2]]
        greedy = pred.submit(prompts[2], max_new_tokens=8)
        beams += [pred.submit(p, max_new_tokens=8, beam=3)
                  for p in prompts[2:]]
        got = [s.result(120) for s in beams]
        greedy.result(120)
    for (ids1, sc1), (ids2, sc2) in zip(solo, got):
        np.testing.assert_array_equal(ids1, ids2)
        np.testing.assert_array_equal(sc1, sc2)
        assert ids1.shape[0] == 3
        # best-first hypothesis ordering
        assert list(sc1) == sorted(sc1, reverse=True)


def test_token_streaming(artifact):
    """submit() yields tokens as steps complete; the iterated stream
    equals the final result."""
    with DecodingPredictor(artifact) as pred:
        stream = pred.submit(_prompts(14, 1)[0], max_new_tokens=9)
        toks = list(stream)
        assert toks == stream.result(10)
        assert 1 <= len(toks) <= 9


def test_prefill_step_cache_consistency(artifact):
    """Teacher-forcing the generated tokens back through the chunked
    prefill programs reproduces the decode-step choices: the two programs
    agree on the cache contents."""
    prompt = _prompts(15, 1)[0][:3]
    with DecodingPredictor(artifact) as pred:
        toks = pred.generate(prompt, max_new_tokens=6)
        for k in range(1, 4):
            forced = np.concatenate([prompt, toks[:k]])
            nxt = pred.generate(forced, max_new_tokens=1)
            assert nxt[0] == toks[k]


def test_deadline_expires_in_queue(artifact):
    with DecodingPredictor(artifact) as pred:
        s = pred.submit(_prompts(16, 1)[0], max_new_tokens=4,
                        deadline_ms=0.0)
        with pytest.raises(DeadlineExceeded):
            s.result(30)
        assert pred.stats.snapshot()['expired'] == 1


def test_deadline_expiry_mid_decode_frees_slot(artifact):
    """A deadline elapsing DURING decode resolves the stream with
    DeadlineExceeded at the next step boundary and frees the slot —
    follow-up traffic is unaffected. Counted, not timed: the deadline
    moves into the past on the scheduler's own thread at the first tick
    that finds three tokens emitted."""
    prompts = _prompts(17, 3)
    with DecodingPredictor(artifact) as pred:
        want = pred.generate(prompts[1], max_new_tokens=5)
        run_tick = pred._run_tick

        def tick(waiting):
            for req in pred._active_requests():
                if req.produced >= 3:
                    req.deadline = 0.0
            run_tick(waiting)
        pred._run_tick = tick
        s = pred.submit(prompts[0], max_new_tokens=57, deadline_ms=3.6e6)
        with pytest.raises(DeadlineExceeded, match='after 3 token'):
            s.result(120)
        pred._run_tick = run_tick
        assert pred.stats.snapshot()['expired'] == 1
        # every slot is free again and serving continues bit-identically
        assert pred._free_slots() == list(range(SLOTS))
        assert pred.generate(prompts[1], max_new_tokens=5) == want


def test_second_token_comes_from_the_tick_after_the_first(artifact):
    """A prompt's last slice is read at the END of its tick, behind the
    running batch's step: the request emits its first token there and
    joins the step of the NEXT tick — one token a tick from then on, the
    first delivered before the second."""
    rng = np.random.RandomState(20)
    prompt = rng.randint(2, VOCAB, 11)      # chunks (4, 8): two slices
    with DecodingPredictor(artifact) as pred:
        run_tick, seen = pred._run_tick, []

        def tick(waiting):
            run_tick(waiting)
            seen.extend((r.next_start, r.prefilling, r.produced)
                        for r in pred._active_requests())
        pred._run_tick = tick
        stream = pred.submit(prompt, max_new_tokens=5)
        got = list(stream)
        pred._run_tick = run_tick
        want = pred.generate(prompt, max_new_tokens=5)
    assert got == want == stream.result(10)
    # after each tick: (prompt tokens prefilled, still prefilling, emitted);
    # the tick that emits the fifth token finishes the request
    assert seen == [(8, True, 0), (11, False, 1), (11, False, 2),
                    (11, False, 3), (11, False, 4)]


def test_a_failing_chunk_program_fails_every_request_loudly(artifact):
    """A slice's dispatch that raises reaches _fail_all inside the tick:
    the stream that was decoding (its step was dispatched in front of the
    slices and is never read), the requests admitting and, as they admit
    in turn, the ones that waited all resolve with the error — none
    hangs — and the endpoint serves again once the program does. Counted,
    not timed: the scheduler's own thread holds the tick after the first
    token until the others are queued, and breaks the chunk programs
    before any of them has a slice dispatched."""
    import threading
    # prompts[0] decodes past three tokens (the mid-decode deadline test's)
    prompts = _prompts(17, 1) + _prompts(21, SLOTS + 2)
    queued = threading.Event()

    def boom(*args):
        raise RuntimeError('chunk program broke')

    with DecodingPredictor(artifact) as pred:
        want = pred.generate(prompts[0], max_new_tokens=6)
        calls = {c: m.call for c, m in pred._chunk_mods.items()}
        run_tick = pred._run_tick

        def tick(waiting):
            if any(r.produced for r in pred._active_requests()):
                assert queued.wait(60)
                for m in pred._chunk_mods.values():
                    m.call = boom
            run_tick(waiting)
        pred._run_tick = tick
        streams = [pred.submit(prompts[0], max_new_tokens=57)]
        assert next(iter(streams[0])) == want[0]    # it holds a slot
        streams += [pred.submit(p, max_new_tokens=6) for p in prompts[1:]]
        queued.set()
        for s in streams:
            with pytest.raises(RuntimeError, match='chunk program broke'):
                s.result(60)
        pred._run_tick = run_tick
        for c, m in pred._chunk_mods.items():
            m.call = calls[c]
        assert pred._free_slots() == list(range(SLOTS))
        assert pred.generate(prompts[0], max_new_tokens=6) == want


def test_max_queue_shedding(artifact):
    """Submissions beyond max_queue waiting requests fast-fail with
    ServerOverloaded before any device work; admitted requests finish."""
    prompts = _prompts(18, 16)
    with DecodingPredictor(artifact, max_queue=4) as pred:
        streams = [pred.submit(p, max_new_tokens=30) for p in prompts]
        shed = served = 0
        for s in streams:
            try:
                s.result(120)
                served += 1
            except ServerOverloaded:
                shed += 1
        snap = pred.stats.snapshot()
    assert shed >= 1 and served >= 4
    assert snap['shed'] == shed and snap['requests'] == served


def test_submit_validation(artifact):
    with DecodingPredictor(artifact) as pred:
        with pytest.raises(ValueError):
            pred.submit([], max_new_tokens=4).result(10)
        with pytest.raises(ValueError):  # longer than the cache
            pred.submit(np.arange(2, CACHE + 3) % VOCAB,
                        max_new_tokens=4).result(10)
        with pytest.raises(ValueError):  # beam wider than the slot pool
            pred.submit([3, 4], beam=SLOTS + 1).result(10)
    with pytest.raises(RuntimeError):
        pred.submit([3, 4])


def test_serving_report_decode_rows(artifact, capsys):
    from paddle_tpu import profiler
    with DecodingPredictor(artifact) as pred:
        pred.generate(_prompts(19, 1)[0], max_new_tokens=4)
        out = profiler.serving_report()
        name = [k for k in out if k.startswith('decode:')]
        assert name, out
        snap = out[name[0]]
    for key in ('tokens', 'tokens_s', 'prefills', 'steps', 'occupancy',
                'ttft_p50_ms', 'ttft_p99_ms', 'itl_p50_ms', 'itl_p99_ms'):
        assert key in snap
    text = capsys.readouterr().out
    assert 'Decode source' in text and 'ttftp99(ms)' in text


def test_warm_fresh_subprocess_zero_compiles(artifact):
    """A fresh serving process loading the sidecar'd artifact performs
    ZERO XLA compiles and produces bit-identical transcripts to an
    in-process run — the ISSUE 8 warm-start acceptance bar."""
    worker = os.path.join(os.path.dirname(__file__),
                          'decode_serve_worker.py')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, worker, artifact, '23', '5', '7'],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    assert 'DECODE_OK' in out.stdout
    payload = json.loads(
        [l for l in out.stdout.splitlines()
         if l.startswith('DECODE ')][0][len('DECODE '):])
    assert payload['compiles'] == 0, payload
    # replicate the worker's prompts in-process and compare transcripts
    rng = np.random.RandomState(23)
    prompts = [rng.randint(2, VOCAB, rng.randint(2, max(CHUNKS) + 1))
               for _ in range(5)]
    with DecodingPredictor(artifact) as pred:
        want = [pred.submit(p, max_new_tokens=7) for p in prompts]
        want = [s.result(120) for s in want]
        ids, scores = pred.generate(prompts[0], max_new_tokens=7, beam=3)
    assert payload['greedy'] == want
    np.testing.assert_array_equal(np.asarray(payload['beam_ids']), ids)
    np.testing.assert_array_equal(np.asarray(payload['beam_scores']),
                                  scores)
