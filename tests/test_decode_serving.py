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
from paddle_tpu.inference.decoding import TokenStream

from decode_feed_check import (alone_slices, fits_first, watch_feed,
                               watch_slices, gated as _gated)

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
    # 2 layers x K/V, then the ids row (each slot's last id, handed
    # from dispatch to dispatch on the device)
    assert len(sig['state']) == 5
    for e in sig['state'][:-1]:
        assert e['shape'][:2] == [SLOTS * (CACHE // BLOCK) + 1, BLOCK]
    assert sig['state'][-1] == {'name': 'decode_ids_row', 'shape': [SLOTS],
                                'dtype': 'int32'}
    assert [e['name'] for e in sig['chunk'][str(CHUNKS[0])]['feeds']][-1] \
        == 'slot'
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
    """A prompt's last slice is read a tick after its dispatch, behind
    the step that already decodes the request (its first token went from
    slice to step on the device): the first token is emitted there, the
    second a tick later — one token a tick from then on, the first
    delivered before the second."""
    rng = np.random.RandomState(20)
    prompt = rng.randint(2, VOCAB, 11)      # chunks (4, 8): two slices
    with DecodingPredictor(artifact) as pred:
        run_tick, seen = pred._run_tick, []

        def tick(waiting):
            run_tick(waiting)
            seen.extend((r.next_start, r.prefilling, r.dispatched,
                         r.produced) for r in pred._active_requests())
        pred._run_tick = tick
        stream = pred.submit(prompt, max_new_tokens=5)
        got = list(stream)
        pred._run_tick = run_tick
        want = pred.generate(prompt, max_new_tokens=5)
        snap = pred.stats.snapshot()
    assert got == want == stream.result(10)
    # after each tick: (prompt tokens prefilled, still prefilling, tokens
    # dispatched, tokens emitted): emitted runs one tick behind
    # dispatched, the fifth token is dispatched in the tick that emits
    # the third, and the tick that emits the fifth finishes the request
    assert seen == [(8, True, 0, 0), (11, False, 1, 0), (11, False, 2, 1),
                    (11, False, 3, 2), (11, False, 4, 3), (11, False, 5, 4)]
    # every step of both requests was dispatched with a read outstanding
    assert snap['steps'] == snap['steps_ahead'] == 8
    assert snap['wasted_rows'] == 0


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

    def boom(*args, **kw):
        raise RuntimeError('chunk program broke')

    with DecodingPredictor(artifact) as pred:
        want = pred.generate(prompts[0], max_new_tokens=6)
        # every chunk program: a slice alone takes its bucket's, the
        # slices of several admitting requests the row program
        mods = list(pred._chunk_mods.values()) + [pred._row_mod]
        calls = [m.call for m in mods]
        run_tick = pred._run_tick

        def tick(waiting):
            if any(r.produced for r in pred._active_requests()):
                assert queued.wait(60)
                for m in mods:
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
        for m, call in zip(mods, calls):
            m.call = call
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
                'ttft_p50_ms', 'ttft_p99_ms', 'itl_p50_ms', 'itl_p99_ms',
                'emit_gap_p50_ms', 'emit_gap_p99_ms', 'ttft_queue_p50_ms',
                'ttft_prefill_p99_ms', 'ttft_read_p99_ms'):
        assert key in snap
    text = capsys.readouterr().out
    assert 'Decode source' in text and 'ttftp99(ms)' in text
    assert 'gapp99(ms)' in text and 'pfillp50' in text


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


# -- one step ahead (ISSUE 31): a tick reads what the tick before dispatched --

def _mixed_batch(seed=31):
    """Seven prompts over 4 slots: one of 19 tokens (three slices of the
    (4, 8) chunks), short ones beside it, three more than there are
    slots (a freed slot is re-admitted)."""
    rng = np.random.RandomState(seed)
    lens = (5, 19, 3, 7, 2, 6, 4)
    return [rng.randint(2, VOCAB, n) for n in lens], (9, 6, 12, 5, 12, 7, 8)


def _serve_batch(pred, prompts, max_new, settled):
    if settled:
        # the synchronous order: the same code, the read taken before
        # the next dispatch — what a live beam or a drafter asks for
        pred._results_first = lambda: True
    pred.stats.reset()
    watch = watch_feed(pred)
    gate = _gated(pred)
    streams = [pred.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, max_new)]
    gate.set()
    got = [list(s.result(120)) for s in streams]
    return got, pred.stats.snapshot(), watch


def test_ahead_and_settled_orders_serve_the_same_transcripts(artifact):
    """A mixed batch — staggered admissions, a 3-slice prompt, a row that
    ends by eos mid-batch with its slot re-admitted, rows that end by
    max_new — decodes to the same tokens whether each step is dispatched
    before the previous one is read (the scheduler's order for greedy
    rows) or after it (forced here, on the instance), and to what each
    request decodes to alone. The counters are exact."""
    prompts, max_new = _mixed_batch()
    with DecodingPredictor(artifact) as pred:
        solo = [pred.generate(p, max_new_tokens=n)
                for p, n in zip(prompts, max_new)]
    # eos is the host's to see: take the artifact's where a transcript
    # ends by it mid-batch while another runs to max_new, else name a
    # token that does (told to the instance, like the order)

    def cut(eos):
        return [t[:t.index(eos) + 1] if eos in t else t for t in solo]

    def mixed(eos):
        ends = [len(t) < n for t, n in zip(cut(eos), max_new)]
        return any(ends) and not all(ends)
    eos = next(e for e in [1] + sorted({t for row in solo for t in row})
               if mixed(e))
    want = cut(eos)
    early = sum(1 for t, n in zip(want, max_new) if len(t) < n)
    snaps = {}
    for settled in (False, True):
        with DecodingPredictor(artifact) as pred:
            pred._eos = eos
            got, snaps[settled], watch = _serve_batch(
                pred, prompts, max_new, settled)
            assert _pool_is_empty(pred)
        assert got == want, settled
        # the step's feed is kept between ticks: at every step it was
        # what a rebuild from the requests gives (watch_feed), and the
        # rows the scheduler re-wrote are exactly those with an event
        snap = snaps[settled]
        assert snap['feed_rows_live'] == watch.live > 0
        assert snap['feed_rows_touched'] == watch.events
        assert 0 < watch.events < watch.live
    ahead, settled = snaps[False], snaps[True]
    tokens = sum(len(t) for t in want)
    for snap in (ahead, settled):
        assert snap['tokens'] == tokens and snap['requests'] == len(want)
        assert snap['slice_reads'] == len(want)
        assert snap['chunk_slices'] == len(want) + 2      # 19 = 8 + 8 + 3
    # every greedy step is dispatched with the tick before it unread; a
    # row whose eos is seen a tick late rides ONE more step
    assert ahead['steps_ahead'] == ahead['steps'] > 0
    assert ahead['wasted_rows'] == early
    assert settled['steps_ahead'] == settled['wasted_rows'] == 0
    # a request's first token comes from its slice, every other from a
    # step row; the wasted rows are the only rows that gave none
    for snap in (ahead, settled):
        rows = int(round(snap['occupancy'] * snap['steps'] * SLOTS))
        assert rows == tokens - len(want) + snap['wasted_rows']


def _pool_is_empty(pred):
    pred.block_manager.evict_all_prefixes()
    return pred.block_manager.stats()['blocks_in_use'] == 0 \
        and pred._free_slots() == list(range(SLOTS))


@pytest.mark.parametrize('what', ['cancel', 'deadline', 'drain', 'close',
                                  'shed'])
def test_an_outstanding_read_strands_no_stream_and_leaks_no_block(
        artifact, what):
    """cancel, an expired deadline, drain, close and a block-pool shed,
    each arriving while the last tick's step is unread (on the
    scheduler's own thread, at a tick that finds a read outstanding and
    two tokens out): every stream ends — with its tokens or its error —
    and every block comes back to the pool."""
    prompts, max_new = _mixed_batch(32)
    fired = []

    def fire(pred):
        target = next((r for r in pred._active_requests()
                       if r.produced >= 2 and r.dispatched < r.max_new),
                      None)
        if fired or pred._unread is None or pred._unread[0] is None \
                or target is None:
            return
        fired.append(target)
        if what == 'cancel':
            target.stream.cancel()
        elif what == 'deadline':
            target.deadline = 0.0
        elif what == 'drain':
            pred._draining = True       # what drain() sets before it waits
        elif what == 'close':
            pred.close()                # on the scheduler's thread: no join
        elif what == 'shed':
            reserve = pred._blocks.reserve

            def once(n):
                pred._blocks.reserve = reserve
                return False            # pressure: the youngest row goes
            pred._blocks.reserve = once

    with DecodingPredictor(artifact) as pred:
        want = [pred.generate(p, max_new_tokens=n)
                for p, n in zip(prompts, max_new)]
        watch = watch_feed(pred)
        gate = _gated(pred, before=fire)
        streams = [pred.submit(p, max_new_tokens=n, deadline_ms=3.6e6)
                   for p, n in zip(prompts, max_new)]
        gate.set()
        ends = []
        for s in streams:
            try:
                ends.append(list(s.result(120)))
            except Exception as e:
                ends.append(e)
        assert fired and all(s.done() for s in streams)
        errors = [e for e in ends if isinstance(e, Exception)]
        # who finished has the tokens it decodes to alone
        assert all(e == w for e, w in zip(ends, want)
                   if not isinstance(e, Exception))
        if what in ('cancel', 'deadline', 'shed'):
            assert len(errors) == 1
            assert type(errors[0]).__name__ == {
                'cancel': 'RuntimeError', 'deadline': 'DeadlineExceeded',
                'shed': 'MidStreamEvicted'}[what]
        elif what == 'drain':
            # active streams finish; the waiting ones are shed at the door
            assert errors and all(isinstance(e, ServerOverloaded)
                                  for e in errors)
            assert pred.drain(60)
        else:
            assert errors and all('closed' in str(e) for e in errors)
        assert pred._unread is None
        assert _pool_is_empty(pred)
        # every feed was what a rebuild from the requests gives, and
        # every row of the kept feed is idle again
        assert watch.steps > 0 and not pred._feed_live.any()
        assert not pred._feed_pos.any() and not pred._feed_tokens.any()
        assert (pred._feed_tables == pred._trash).all()
        if what != 'close':
            pred._draining = False
            assert pred.generate(prompts[0], max_new_tokens=9) == want[0]


# -- the step's feed kept between ticks (ISSUE 35) ----------------------------

def test_kept_feed_counts_the_rows_it_rewrites(artifact):
    """Three greedy requests over pages of 4 rows, counted by hand: a row
    is re-written in its first step and where its position opens a page,
    and rides the kept arrays otherwise."""
    rng = np.random.RandomState(35)
    prompts = [rng.randint(2, VOCAB, n) for n in (5, 8, 3)]
    with DecodingPredictor(artifact) as pred:
        pred._eos = -1                      # every row runs to max_new
        watch = watch_feed(pred)
        gate = _gated(pred)
        streams = [pred.submit(p, max_new_tokens=9) for p in prompts]
        gate.set()
        assert all(len(s.result(120)) == 9 for s in streams)
        snap = pred.stats.snapshot()
        assert _pool_is_empty(pred)
    # a request's first token comes from its slice, the other 8 from steps
    assert snap['feed_rows_live'] == watch.live == 3 * 8
    # positions written by steps: 5..12, 8..15, 3..10 — the first of each
    # (its first step) and every multiple of 4 behind it
    assert snap['feed_rows_touched'] == watch.events == (1 + 2) + 2 + (1 + 2)
    assert snap['wasted_rows'] == 0


def test_kept_feed_under_a_prefix_hit_and_a_beam(artifact):
    """The rows the arrays cannot advance blindly take the per-row route
    through the same arrays: a request admitted on a prefix hit (its
    table holds shared blocks), a beam (reorders: tables permuted, blocks
    copied on write, finished beams idle) beside greedy rows — every feed
    equals the rebuild, transcripts are the solo ones, every block comes
    back."""
    rng = np.random.RandomState(36)
    prompt = rng.randint(2, VOCAB, 9)           # 2 full pages + 1
    other = rng.randint(2, VOCAB, 6)
    with DecodingPredictor(artifact) as pred:
        solo = pred.generate(prompt, max_new_tokens=10)
        solo_other = pred.generate(other, max_new_tokens=7)
        ids, scores = pred.generate(other, max_new_tokens=8, beam=3)
        pred.stats.reset()
        watch = watch_feed(pred)
        hit = pred.submit(prompt, max_new_tokens=10)   # the pages are shared
        assert hit.result(120) == solo
        snap = pred.stats.snapshot()
        assert snap['prefix_hits'] == 1
        # the one request is re-written every step, and its shared pages
        # are never written: nothing is copied
        assert snap['feed_rows_touched'] == snap['feed_rows_live'] == 9
        assert snap['cow_blocks'] == 0
        beam = pred.submit(other, max_new_tokens=8, beam=3)
        greedy = pred.submit(other, max_new_tokens=7)
        got_ids, got_scores = beam.result(120)
        assert greedy.result(120) == solo_other
        snap = pred.stats.snapshot()
        assert watch.steps > 9 and snap['feed_rows_live'] == watch.live
        assert snap['feed_rows_touched'] == watch.events
        assert snap['cow_blocks'] > 0 and snap['reorders'] > 0
        assert _pool_is_empty(pred)
    np.testing.assert_array_equal(got_ids, ids)
    np.testing.assert_array_equal(got_scores, scores)


# -- one dispatch for a tick's prefill slices (ISSUE 39): the row program ----

def _export_wide(out, rows=True):
    """The module's spec with eight slots; `rows` False: its row program
    taken out before the export."""
    from models.transformer import build_decode_spec
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(
            vocab=VOCAB, d_model=16, n_head=2, n_layer=2, d_ff=32,
            max_slots=8, max_cache_len=CACHE, chunk_sizes=CHUNKS,
            block_size=BLOCK, eos_id=1)
        spec['startup'].random_seed = 3
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        assert (spec['chunk_rows']['size'], spec['chunk_rows']['rows']) \
            == (8, 4)
        if not rows:
            del spec['chunk_rows']
        export_decode(spec, out, scope=scope)
    return out


@pytest.fixture(scope='module')
def wide(tmp_path_factory):
    """Eight slots: room for more admitting requests in one tick than the
    row program (8 x 4) has rows."""
    return _export_wide(str(tmp_path_factory.mktemp('decode_wide') / 'art'))


@pytest.fixture(scope='module')
def rowless(tmp_path_factory):
    """The same spec exported WITHOUT its row program: what every artifact
    from before the row program is."""
    return _export_wide(
        str(tmp_path_factory.mktemp('decode_rowless') / 'art'), rows=False)


def _row_prompts(n, seed=41):
    """Prompts of one to three slices of the (4, 8) chunks."""
    rng = np.random.RandomState(seed)
    lens = (3, 11, 6, 8, 19, 5, 9, 2, 14, 7)[:n]
    return [rng.randint(2, VOCAB, k) for k in lens]


def _held_behind(pred, prompt, max_new, before=None):
    """Submit `prompt`, wait until it decodes, then hold the scheduler
    (_gated): what the test queues before it sets the gate is all found
    waiting by ONE tick, beside a running batch of one. Returns (the
    stream, its first token, the gate)."""
    stream = pred.submit(prompt, max_new_tokens=max_new)
    tokens = iter(stream)
    head = next(tokens)
    return stream, head, _gated(pred, before)


def _together(pred, prompts, max_new=7):
    """The prompts served together: the first decodes, all the others
    admit in one tick (as many as there are slots)."""
    pred.block_manager.evict_all_prefixes()
    pred.stats.reset()
    watch = watch_feed(pred)
    first, head, gate = _held_behind(pred, prompts[0], max_new)
    streams = [pred.submit(p, max_new_tokens=max_new) for p in prompts[1:]]
    gate.set()
    got = [[head] + list(first)] + [list(s.result(120)) for s in streams]
    return got, pred.stats.snapshot(), watch


@pytest.mark.parametrize('n', [3, 5, 8, 10])
def test_slices_due_together_ride_one_call_and_change_no_token(wide, n):
    """N requests submitted together give the transcripts of the same
    requests served one slice per dispatch (the same artifact, told on
    the instance that it has one row a call) and of each served alone, in fewer
    calls than slices; every kept feed equals its rebuild."""
    prompts = _row_prompts(n)
    with DecodingPredictor(wide) as pred:
        assert pred._rows == 4 and pred._row_mod.name == 'chunk_8x4'
        solo = [pred.generate(p, max_new_tokens=7) for p in prompts]
        alone = pred.stats.snapshot()
        rows, snap, watch = _together(pred, prompts)
        assert watch.steps > 0
        pred._rows = 1      # as an artifact without a row program
        pred._run_tick = type(pred)._run_tick.__get__(pred)
        pred._step_feed = type(pred)._step_feed.__get__(pred)
        single, snap1, _ = _together(pred, prompts)
    assert rows == single == solo
    # a request alone: every slice is a call of its own
    assert alone['chunk_dispatches'] == alone['chunk_slices'] > n
    assert snap1['chunk_dispatches'] == snap1['chunk_slices'] \
        == snap['chunk_slices']
    assert snap['chunk_dispatches'] < snap['chunk_slices']
    assert snap['slice_reads'] == snap['requests'] == n
    assert snap['prefills'] == snap['chunk_slices']


def test_a_mixed_tick_of_rows(wide):
    """One tick with seven slices due beside a decoding row: six of the
    largest bucket — greedy rows, a row admitted on a prefix hit (start
    > 0), a row whose request is cancelled between its dispatch and its
    read (the read drops it: _holds) — and one of the small bucket. The
    tick's budget is one call of the row program (4 x 8 tokens): the four
    oldest go in it, the other three wait a tick and then ride with the
    13-token prompt's second slice, three rows in the row program and the
    small bucket's in a call of its own. Every other transcript is the
    solo one, every feed equals its rebuild, every block comes back."""
    rng = np.random.RandomState(43)
    shared = rng.randint(2, VOCAB, 8)                   # two full pages
    hit = np.concatenate([shared[:4], rng.randint(2, VOCAB, 5)])
    prompts = [rng.randint(2, VOCAB, k) for k in (5, 7, 13, 6, 8)] \
        + [hit, rng.randint(2, VOCAB, 3)]
    calls = []
    with DecodingPredictor(wide) as pred:
        solo = [pred.generate(p, max_new_tokens=6) for p in prompts]
        pred.block_manager.evict_all_prefixes()
        pred.generate(shared, max_new_tokens=2)         # publishes its pages
        pred.stats.reset()
        watch = watch_feed(pred)
        dispatch_rows = pred._dispatch_rows

        def counted(n, **kw):
            calls.append((n, pred._row_feed['start'][:n, 0].tolist()))
            return dispatch_rows(n, **kw)
        pred._dispatch_rows = counted
        streams = []

        def cancel_behind_the_dispatch(pred):
            # on the scheduler's thread, in front of the tick that reads
            # what the last one dispatched
            if streams and pred._unread is not None and pred._unread[1]:
                streams[3].cancel()
        first, _, gate = _held_behind(pred, shared[:3], 40,
                                      before=cancel_behind_the_dispatch)
        streams += [pred.submit(p, max_new_tokens=6) for p in prompts]
        gate.set()
        got = []
        for k, stream in enumerate(streams):
            if k == 3:
                with pytest.raises(RuntimeError, match='cancelled'):
                    stream.result(120)
                got.append(None)
            else:
                got.append(list(stream.result(120)))
        first.result(120)
        snap = pred.stats.snapshot()
        assert watch.steps > 0 and _pool_is_empty_of(pred, 8)
    assert [g for k, g in enumerate(got) if k != 3] \
        == [s for k, s in enumerate(solo) if k != 3]
    # six slices of the largest bucket due in one tick: four rows go,
    # the budget is spent; a tick later the 13-token prompt's second
    # slice (the oldest), the 8-token prompt's and the hit's, whose row
    # starts behind its shared page. The 3-token prompt's slice and the
    # first request's are calls of their own
    assert calls == [(4, [0, 0, 0, 0]), (3, [8, 0, 4])]
    assert snap['prefix_hits'] == 1
    assert snap['chunk_slices'] == 9 and snap['chunk_dispatches'] == 4
    assert snap['slices_deferred'] == 3
    assert snap['steps_ahead'] == snap['steps']     # no beam: a tick late
    # the cancelled row was dispatched and never read
    assert snap['slice_reads'] == 7


def _pool_is_empty_of(pred, slots):
    pred.block_manager.evict_all_prefixes()
    return pred.block_manager.stats()['blocks_in_use'] == 0 \
        and pred._free_slots() == list(range(slots))


def test_a_beam_among_greedy_rows_keeps_the_one_row_program(wide):
    """A beam admitted in one tick with four greedy prompts: the tick's
    budget holds four slices — the beam's and the three greedy ones
    ahead of the last, which waits a tick. The greedy slices are one call
    of the row program, read once and ids only; the
    beam's slice is a call of its own bucket's one-row program, its
    [1, V] logits row copied — hypotheses AND scores as served alone, to
    the bit; the greedy transcripts the solo ones."""
    prompts = _row_prompts(5, seed=47)
    with DecodingPredictor(wide) as pred:
        solo = [pred.generate(p, max_new_tokens=6) for p in prompts]
        ids, scores = pred.generate(prompts[2], max_new_tokens=6, beam=3)
        pred.block_manager.evict_all_prefixes()
        pred.stats.reset()
        watch = watch_feed(pred)
        reads = []
        to_host = pred._to_host

        def seen(read):
            out = to_host(read)
            reads.append((read[0], None if out[1] is None
                          else out[1].shape))
            return out
        pred._to_host = seen
        first, head, gate = _held_behind(pred, prompts[0], 6)
        greedy = [pred.submit(p, max_new_tokens=6) for p in prompts[1:3]]
        beam = pred.submit(prompts[2], max_new_tokens=6, beam=3)
        greedy += [pred.submit(p, max_new_tokens=6) for p in prompts[3:]]
        gate.set()
        got_ids, got_scores = beam.result(120)
        assert [[head] + list(first)] \
            + [list(s.result(120)) for s in greedy] == solo
        snap = pred.stats.snapshot()
        assert watch.steps > 0 and _pool_is_empty_of(pred, 8)
    np.testing.assert_array_equal(got_ids, ids)
    np.testing.assert_array_equal(got_scores, scores)
    # five slices due in one tick, four go: the three greedy ones are one
    # call of the row program, read without its logits; the beam's is its
    # own, and the only chunk call whose logits anybody reads
    chunk_reads = [r for r in reads if r[0].startswith('chunk')]
    assert ('chunk_8x4', None) in chunk_reads
    assert [r for r in chunk_reads if r[1] is not None] \
        == [('chunk_8', (1, VOCAB))]
    assert snap['chunk_dispatches'] <= snap['chunk_slices'] - 2
    assert snap['slices_deferred'] >= 1


def test_an_artifact_without_a_row_program_serves_as_it_did(rowless, wide):
    """No 'chunk_rows' in the signature (an artifact exported before the
    row program, or a spec without one): it loads, every slice is a call
    of its own, and the transcripts are those of the artifact that has
    one."""
    import json
    from paddle_tpu.inference import decoding
    with open(os.path.join(rowless, decoding._DECODE_SIGNATURE)) as f:
        assert 'chunk_rows' not in json.load(f)
    assert not os.path.exists(os.path.join(rowless, 'prefill_chunk_00008x4'))
    assert os.path.exists(os.path.join(
        wide, 'prefill_chunk_00008x4', 'aot_cpu.jaxexec'))
    prompts = _row_prompts(6)
    with DecodingPredictor(wide) as pred:
        want, with_rows, _ = _together(pred, prompts)
        assert 'chunk_8x4' in pred.attention_bodies
    with DecodingPredictor(rowless) as pred:
        assert pred._row_mod is None and pred._rows == 1
        got, snap, _ = _together(pred, prompts)
        assert 'chunk_8x4' not in pred.attention_bodies
        pred.warmup()
    assert got == want
    assert snap['chunk_dispatches'] == snap['chunk_slices'] \
        == with_rows['chunk_slices'] > with_rows['chunk_dispatches']


def test_warmup_runs_the_row_program_on_pad_rows(wide):
    """warmup() touches every program, the row program by a call of pad
    rows: it writes the trash block, no slot's id, and counts for
    nothing."""
    with DecodingPredictor(wide) as pred:
        seen = []
        call = pred._row_mod.call

        def watched(*args, **kw):
            seen.append((kw['rows'], [np.asarray(a).copy()
                                      for a in args[-1]]))
            return call(*args, **kw)
        pred._row_mod.call = watched
        pred.warmup()
        (rows, feeds), = seen
        by_name = dict(zip(pred._row_feed, feeds))
        assert rows == 0 and not by_name['chunk_len'].any()
        assert (by_name['slot'] == -1).all()
        assert (by_name['block_table'] == pred._trash).all()
        snap = pred.stats.snapshot()
        assert snap['chunk_dispatches'] == snap['chunk_slices'] == 0
        assert pred.generate(_row_prompts(1)[0], max_new_tokens=4)


@pytest.fixture(scope='module')
def windowed(tmp_path_factory):
    """A toy artifact with sliding-window layers (grouped heads, a second
    table): four slots, chunks (8, 16), no row program."""
    from models.exaone_moe import build_decode_spec
    art = str(tmp_path_factory.mktemp('decode_window') / 'art')
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(n_layer=2, kv_cache_dtype='float32',
                                 weights_dtype='float32')
        assert 'window' in spec and 'chunk_rows' not in spec
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        export_decode(spec, art, scope=scope, precompile=False)
    return art


def test_a_window_artifact_never_batches(windowed):
    """Window layers (and grouped heads) keep every chunk op on the body
    that has no rows: the spec holds no row program, and several
    admissions in one tick are a call each."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(2, 120, k) for k in (5, 20, 9)]
    with DecodingPredictor(windowed) as pred:
        assert pred._row_mod is None
        solo = [pred.generate(p, max_new_tokens=4) for p in prompts]
        pred.stats.reset()
        first, head, gate = _held_behind(pred, prompts[0], 4)
        streams = [pred.submit(p, max_new_tokens=4) for p in prompts[1:]]
        gate.set()
        assert [[head] + list(first)] \
            + [list(s.result(120)) for s in streams] == solo
        snap = pred.stats.snapshot()
    assert snap['chunk_dispatches'] == snap['chunk_slices'] == 4


# -- a tick's prefill budget (ISSUE 52): one largest chunk call's worth --------

# per artifact: (prompt lengths — the first decodes, the others admit in
# ONE tick, each of two or three slices; its tokens; theirs; vocabulary)
_BUDGET_CASES = {
    # rows 1, chunks (4, 8): a tick holds 8 prompt tokens by bucket
    'rowless': ((3, 19, 17, 23, 9, 20, 12, 18), 40, 6, VOCAB),
    # rows 4: a tick holds 32, one call of the row program
    'wide': ((3, 19, 17, 23, 9, 20, 12, 18), 40, 6, VOCAB),
    # window layers, rows 1, chunks (8, 16): a tick holds 16
    'windowed': ((5, 40, 33, 20), 60, 5, 120),
}


def _onto_a_decoding_batch(pred, prompts, first_new, max_new):
    """prompts[0] decodes for `first_new` tokens — past every other
    prompt's prefill — and the others are found waiting by ONE tick.
    Returns (transcripts, watch_slices' ticks, the snapshot, the tick
    log, watch_feed's count)."""
    pred.block_manager.evict_all_prefixes()
    pred.stats.reset()
    feed, ticks = watch_feed(pred), watch_slices(pred)
    first, head, gate = _held_behind(pred, prompts[0], first_new)
    streams = [pred.submit(p, max_new_tokens=max_new) for p in prompts[1:]]
    gate.set()
    got = [[head] + list(first)] + [list(s.result(120)) for s in streams]
    assert pred.drain(60)
    return got, ticks, pred.stats.snapshot(), pred.stats.tick_log(), feed


@pytest.fixture(scope='module', params=sorted(_BUDGET_CASES))
def budgeted(request):
    """Several prompts of several slices admitted at once onto a decoding
    batch, on an artifact without a row program, with one, and with
    window layers: served alone, together under the tick's budget, and
    together in the parent's order (no budget: every due slice, every
    tick)."""
    lens, first_new, max_new, vocab = _BUDGET_CASES[request.param]
    rng = np.random.RandomState(52)
    prompts = [rng.randint(2, vocab, n) for n in lens]
    news = [first_new] + [max_new] * (len(prompts) - 1)
    out = {'prompts': prompts, 'news': news}
    art = request.getfixturevalue(request.param)
    with DecodingPredictor(art) as pred:
        pred.stats.reset()
        out['solo'] = [list(pred.generate(p, max_new_tokens=n))
                       for p, n in zip(prompts, news)]
        out['solo_snap'] = pred.stats.snapshot()
        out['budget'] = pred._rows * pred._chunks[-1]
        out['chunks'] = pred._chunks
    with DecodingPredictor(art) as pred:
        (out['got'], out['ticks'], out['snap'], out['log'],
         out['feed']) = _onto_a_decoding_batch(pred, prompts, first_new,
                                               max_new)
        out['requests'] = pred.stats.request_log()
        out['left_clean'] = _pool_is_empty_of(pred, pred.max_slots)
    with DecodingPredictor(art) as pred:
        pred._prefill_budget = lambda: float('inf')
        out['parent'], out['parent_ticks'] = _onto_a_decoding_batch(
            pred, prompts, first_new, max_new)[:2]
    return out


def _tokens(went):
    return sum(bucket for _, bucket, _ in went)


def test_a_ticks_slices_fit_one_call_of_the_largest_chunk(budgeted):
    """While a row decodes, the bucket sizes of the slices a tick
    dispatches add up to no more than rows x largest chunk — where the
    parent's order, on the same traffic, dispatched more."""
    bound = [t for t in budgeted['ticks'] if t['decoding']]
    assert len(bound) > 3 and budgeted['feed'].steps > len(bound)
    assert all(0 < _tokens(t['went']) <= budgeted['budget'] for t in bound)
    assert any(len(t['went']) < len(t['due']) for t in bound)
    assert max(_tokens(t['went']) for t in budgeted['parent_ticks']
               if t['decoding']) > budgeted['budget']
    assert all(t['went'] == t['due'] for t in budgeted['parent_ticks'])


def test_slices_go_oldest_first_and_the_head_always_goes(budgeted):
    """A tick's slices are chosen in admission order, each where its own
    bucket fits what is left: the oldest always goes, one that does not
    fit waits, a smaller one behind it may go."""
    for t in budgeted['ticks']:
        if t['decoding']:
            assert t['went'] == fits_first(t['due'], budgeted['budget'])
            assert t['went'][0] == t['due'][0]
        seqs = [seq for seq, _, _ in t['due']]
        assert seqs == sorted(seqs)


def test_a_deferred_request_keeps_the_slices_it_takes_alone(budgeted):
    """Delayed, never reshaped: every prompt finishes, in the buckets and
    at the starts it has with nobody beside it."""
    by_request = {}
    for t in budgeted['ticks']:
        for seq, bucket, start in t['went']:
            by_request.setdefault(seq, []).append((bucket, start))
    want = [alone_slices(budgeted['chunks'], len(p))
            for p in budgeted['prompts']]
    assert [by_request[seq] for seq in sorted(by_request)] == want
    assert budgeted['snap']['chunk_slices'] == sum(len(w) for w in want)
    assert budgeted['snap']['requests'] == len(want)
    assert budgeted['left_clean']


def test_transcripts_under_the_budget_are_the_solo_and_the_parents(budgeted):
    """Bit-identical to each request served alone, and to all of them
    served in the parent's order."""
    assert budgeted['got'] == budgeted['solo'] == budgeted['parent']
    assert [len(t) for t in budgeted['got']] == budgeted['news']


def test_slices_deferred_counts_what_waited(budgeted):
    """One count per due slice per tick it waited, in the counter and in
    the tick log's column; 0 where one request admits at a time."""
    waited = sum(len(t['due']) - len(t['went']) for t in budgeted['ticks'])
    snap, log = budgeted['snap'], budgeted['log']
    assert snap['slices_deferred'] == waited > 0
    assert log['deferred'].sum() == waited
    assert log['slices'].sum() == snap['chunk_slices']
    assert np.all(log['deferred'][log['slices'] == 0] == 0)
    assert budgeted['solo_snap']['slices_deferred'] == 0
    assert budgeted['solo_snap']['chunk_slices'] == snap['chunk_slices']


@pytest.mark.parametrize('which', sorted(_BUDGET_CASES))
def test_with_no_decoding_row_every_due_slice_goes_in_one_tick(request,
                                                               which):
    """The first tick of a ramp: nobody decodes, there is no gap to keep,
    and every admitted prompt's first slice goes — more than the budget
    holds; once a row decodes the budget holds again."""
    lens, _, max_new, vocab = _BUDGET_CASES[which]
    rng = np.random.RandomState(53)
    # a one-slice prompt among them: it decodes from the second tick on
    prompts = [rng.randint(2, vocab, n) for n in (3,) + lens[1:]]
    with DecodingPredictor(request.getfixturevalue(which)) as pred:
        solo = [list(pred.generate(p, max_new_tokens=max_new))
                for p in prompts]
        pred.block_manager.evict_all_prefixes()
        pred.stats.reset()
        ticks, gate = watch_slices(pred), _gated(pred)
        streams = [pred.submit(p, max_new_tokens=max_new) for p in prompts]
        gate.set()
        assert [list(s.result(120)) for s in streams] == solo
        budget = pred._rows * pred._chunks[-1]
        log = pred.stats.tick_log()
    first = ticks[0]
    assert not first['decoding'] and first['went'] == first['due']
    assert len(first['went']) == len(prompts)
    assert _tokens(first['went']) > budget
    assert log['deferred'][log['slices'] > 0][0] == 0
    assert ticks[1]['decoding'] and _tokens(ticks[1]['went']) <= budget
    assert len(ticks[1]['went']) < len(ticks[1]['due'])


@pytest.mark.parametrize('which', ['rowless', 'windowed'])
@pytest.mark.parametrize('how', ['cancel', 'expire'])
def test_a_deferred_request_that_ends_mid_prefill_gives_everything_back(
        request, which, how):
    """A request that has waited for room in a tick and is cancelled, or
    passes its deadline, before its first slice: it fails as any
    admitting request does, its slots and blocks (the window layers' too)
    come back, and the requests around it are served what they are served
    alone."""
    lens, first_new, max_new, vocab = _BUDGET_CASES[which]
    rng = np.random.RandomState(54)
    prompts = [rng.randint(2, vocab, n) for n in lens[:4]]
    ended = []
    with DecodingPredictor(request.getfixturevalue(which)) as pred:
        solo = [list(pred.generate(p, max_new_tokens=max_new))
                for p in prompts[1:]]
        pred.block_manager.evict_all_prefixes()
        pred.stats.reset()
        streams = []

        def end_the_youngest(pred):
            # on the scheduler's thread, in front of a tick: the last
            # admitted request holds a slot, has waited, has no slice yet
            req = next((r for r in pred._active_requests()
                        if r.stream is streams[-1]), None) \
                if streams else None
            if ended or req is None or not pred.stats.slices_deferred:
                return
            assert req.prefilling and req.next_start == 0 and req.tables
            ended.append(list(req.slots))
            if how == 'cancel':
                req.stream.cancel()
            else:
                req.deadline = time.perf_counter() - 1.0
        first, _, gate = _held_behind(pred, prompts[0], first_new,
                                      before=end_the_youngest)
        streams += [pred.submit(p, max_new_tokens=max_new)
                    for p in prompts[1:]]
        gate.set()
        assert [list(s.result(120)) for s in streams[:-1]] == solo[:-1]
        with pytest.raises(RuntimeError if how == 'cancel'
                           else DeadlineExceeded,
                           match='cancelled' if how == 'cancel'
                           else 'slot freed'):
            streams[-1].result(120)
        first.result(120)
        assert pred.drain(60)
        snap = pred.stats.snapshot()
        assert _pool_is_empty_of(pred, pred.max_slots)
        if pred._window:
            assert pred.block_manager.stats()['window_blocks_in_use'] == 0
    assert len(ended) == 1 and len(ended[0]) == 1
    assert snap['expired'] == (how == 'expire')
    # nothing of its prompt was ever dispatched
    assert snap['chunk_slices'] == sum(
        len(alone_slices(pred._chunks, len(p))) for p in prompts[:-1])


# -- TokenStream: a delivery is one C call (queue.SimpleQueue) -----------------

def test_tokenstream_batches_keep_order_and_multi_token_pushes():
    s = TokenStream()
    s._push(5)
    s._push_many([6, 7, 8])
    s._push(9)
    s._finish([5, 6, 7, 8, 9])
    assert list(s.batches()) == [[5], [6, 7, 8], [9]]
    assert s.result(1) == [5, 6, 7, 8, 9] and s.done()
    t = TokenStream()
    t._push_many([1, 2])
    t._finish([1, 2])
    assert list(t) == [1, 2]


@pytest.mark.parametrize('how', ['finish', 'fail'])
def test_tokenstream_end_wakes_a_blocked_consumer(how):
    import threading
    s, seen = TokenStream(), []

    def consume():
        try:
            seen.extend(s)
            seen.append('end')
        except KeyError as e:
            seen.append(e)
    t = threading.Thread(target=consume, daemon=True)
    t.start()
    s._push(3)
    time.sleep(0.05)            # the consumer blocks in get() again
    s._finish([3]) if how == 'finish' else s._fail(KeyError('boom'))
    t.join(30)
    assert not t.is_alive()
    assert seen[0] == 3 and len(seen) == 2
    if how == 'finish':
        assert seen[1] == 'end' and s.result(1) == [3]
    else:
        assert isinstance(seen[1], KeyError)
        assert isinstance(s.exception(1), KeyError)


def test_tokenstream_64_consumers_lose_no_token():
    """One producer, 64 consumer threads, 200 tokens a stream, under a
    shortened switch interval: every consumer sees its own tokens, all of
    them, in order."""
    import threading
    n_streams, n_tokens = 64, 200
    streams = [TokenStream() for _ in range(n_streams)]
    got = [[] for _ in streams]

    def consume(i):
        got[i].extend(streams[i])
    threads = [threading.Thread(target=consume, args=(i,), daemon=True)
               for i in range(n_streams)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for k in range(n_tokens):           # a step: one token a stream
            for i, s in enumerate(streams):
                s._push(i * n_tokens + k)
        for i, s in enumerate(streams):
            s._finish(None)
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for i in range(n_streams):
        assert got[i] == list(range(i * n_tokens, (i + 1) * n_tokens))


# -- the tick log: every busy tick, with tracing off --------------------------

@pytest.fixture(scope='module')
def ticked(artifact):
    """Six requests served with nothing tracing; what the scheduler's
    ticks added to busy_s, one entry a tick that added anything."""
    from paddle_tpu.inference.decoding import DecodeStats
    added = []
    with DecodingPredictor(artifact) as pred:
        pred.generate(_prompts(31, 1)[0], max_new_tokens=2)    # warm
        # the warm-up's last token is out before its tick has ended: a
        # reset() inside that tick would be followed by the tick's row
        seen = None
        while seen != pred.stats.busy_s:
            seen = pred.stats.busy_s
            time.sleep(0.03)
        pred.stats.reset()
        # a reset forgets the CPU clock's last reading: the next one is a
        # baseline and the one after it the first logged — a run of some
        # tens of milliseconds wants them closer together than 20 ms
        pred.stats.CPU_EVERY_S = 0.004
        emptied = len(pred.stats.tick_log())
        run_tick = pred._run_tick

        def counted(waiting):
            before = pred.stats.busy_s
            run_tick(waiting)
            if pred.stats.busy_s != before:
                added.append(pred.stats.busy_s - before)
        pred._run_tick = counted
        t_first = time.perf_counter()
        streams = [pred.submit(p, max_new_tokens=6)
                   for p in _prompts(32, 6)]
        tokens = [s.result(120) for s in streams]
        pred.close()
        log, snap = pred.stats.tick_log(), pred.stats.snapshot()
        assert isinstance(pred.stats, DecodeStats)
    return {'log': log, 'snap': snap, 'added': added, 'tokens': tokens,
            'requests': pred.stats.request_log(), 'prompts': _prompts(32, 6),
            'busy_s': pred.stats.busy_s, 'emptied': emptied,
            't_first': t_first, 'stats': pred.stats}


def test_tick_log_has_one_row_a_busy_tick(ticked):
    log = ticked['log']
    assert ticked['emptied'] == 0           # reset() emptied the warm-up's
    assert len(log) == len(ticked['added']) > 3
    assert list(log.dtype.names) == ['t0', 'wall_s', 'cpu_s', 'wait_s',
                                     'gc_s', 'dispatches', 'rows',
                                     'cpu_wall_s', 'tick', 'slices',
                                     'deferred', 'emit_t', 'emit_rows',
                                     'wait_step_s', 'wait_slice_s',
                                     'slice_tokens', 'slices_carried']
    # a row carries its tick's number, the 'tick' stat of its span: an
    # idle tick has a span and no row, so the numbers may skip
    assert np.all(np.diff(log['tick']) >= 1)
    assert np.all(np.diff(log['t0']) > 0) and log['t0'][0] >= ticked['t_first']
    np.testing.assert_allclose(log['wall_s'], ticked['added'], rtol=0,
                               atol=1e-9)
    assert log['wall_s'].sum() == pytest.approx(ticked['busy_s'], rel=1e-9)


def test_tick_log_rows_add_up(ticked):
    """Waiting for the device is a part of the tick; every dispatch and
    every token the steps emitted is in some row. The thread's CPU clock
    is read where CPU_EVERY_S have passed since its last reading: such a
    row holds the CPU time since then and the busy seconds that spans —
    its own and those of the rows since — and the others hold neither."""
    log, snap = ticked['log'], ticked['snap']
    assert np.all(log['wait_s'] >= 0) and np.all(log['gc_s'] >= 0)
    assert np.all(log['wait_s'] <= log['wall_s'] + 1e-6)
    # parted by what was waited for: the step's ids, a prompt's last slice
    np.testing.assert_allclose(log['wait_step_s'] + log['wait_slice_s'],
                               log['wait_s'], rtol=0, atol=1e-12)
    assert np.all(log['wait_step_s'] >= 0) and np.all(log['wait_slice_s'] >= 0)
    assert log['wait_slice_s'].sum() > 0 and log['wait_step_s'].sum() > 0
    # calls, not slices: six prompts at once ride the row program
    assert log['dispatches'].sum() \
        == snap['steps'] + snap['chunk_dispatches']
    assert snap['chunk_dispatches'] < snap['chunk_slices']
    assert log['slices'].sum() == snap['chunk_slices']
    assert log['deferred'].sum() == snap['slices_deferred']
    assert log['rows'].sum() == snap['tokens'] \
        == sum(len(t) for t in ticked['tokens'])
    read = ~np.isnan(log['cpu_s'])
    assert np.array_equal(read, ~np.isnan(log['cpu_wall_s']))
    assert 1 <= read.sum() <= len(log)
    # between two readings at least CPU_EVERY_S passed
    ends = (log['t0'] + log['wall_s'])[read]
    assert np.all(np.diff(ends) >= ticked['stats'].CPU_EVERY_S)
    # a reading spans its own tick and the unread ticks before it
    spans = np.split(log['wall_s'], np.flatnonzero(read) + 1)[:read.sum()]
    np.testing.assert_allclose(log['cpu_wall_s'][read][1:],
                               [s.sum() for s in spans][1:], atol=1e-9)
    assert np.all(log['cpu_wall_s'][read] >= log['wall_s'][read] - 1e-9)
    # on a CPU for no longer than the time between the two readings (the
    # loop between two ticks is CPU time and not busy time, so a reading
    # of this toy's 0.2 ms ticks may pass the busy seconds it spans)
    assert np.all(log['cpu_s'][read] >= 0)
    assert np.all(log['cpu_s'][read][1:] <= np.diff(ends) + 1e-4)


def test_tick_log_says_when_a_step_delivered_and_to_how_many(ticked):
    """`emit_t` is the instant a tick's step read stamped on its
    deliveries, NaN where the tick read no step; `emit_rows` the rows it
    delivered to: every token but the requests' first, which come from
    their prompts' last slices and are in `rows` alone."""
    log = ticked['log']
    read = ~np.isnan(log['emit_t'])
    assert np.array_equal(read, log['emit_rows'] > 0) and read.sum() > 3
    assert np.all(log['emit_t'][read] >= log['t0'][read])
    assert np.all(log['emit_t'][read] <= (log['t0'] + log['wall_s'])[read])
    assert np.all(np.diff(log['emit_t'][read]) > 0)
    firsts = len(ticked['tokens'])
    assert log['emit_rows'].sum() == log['rows'].sum() - firsts \
        == sum(len(t) - 1 for t in ticked['tokens'])
    assert np.all(log['emit_rows'] <= log['rows'])
    # a last slice is waited for in the tick that delivers its first token
    assert np.all((log['rows'] - log['emit_rows'])[log['wait_slice_s'] > 0]
                  >= 1)


def test_tick_log_slice_tokens_are_the_buckets_dispatched(budgeted):
    """`slice_tokens`: the prompt tokens, by BUCKET size, of the slices a
    tick dispatched — what the tick's budget counts — against a run whose
    slices are known tick by tick."""
    log, ticks = budgeted['log'], budgeted['ticks']
    went = [_tokens(t['went']) for t in ticks]
    sliced = log[log['slices'] > 0]
    assert sliced['slice_tokens'].tolist() == went
    assert np.all(log['slice_tokens'][log['slices'] == 0] == 0)
    assert log['slice_tokens'].sum() == sum(
        bucket for p in budgeted['prompts']
        for bucket, _ in alone_slices(budgeted['chunks'], len(p)))


def test_the_cpu_clock_is_read_every_tick_where_it_is_always_due(artifact):
    """CPU_EVERY_S = 0 on the instance: every row carries a reading that
    spans that tick alone, and working + waiting for the device fill no
    more than the tick."""
    with DecodingPredictor(artifact) as pred:
        pred.stats.CPU_EVERY_S = 0
        pred.generate(_prompts(33, 1)[0], max_new_tokens=8)
        log = pred.stats.tick_log()
    assert len(log) > 3 and not np.isnan(log['cpu_s'][1:]).any()
    np.testing.assert_allclose(log['cpu_wall_s'][1:], log['wall_s'][1:],
                               atol=1e-9)
    assert np.all(log['cpu_s'][1:] <= log['wall_s'][1:] + 1e-3)


def test_snapshot_reads_the_tick_log(ticked):
    log, snap = ticked['log'], ticked['snap']
    assert snap['tick_max_ms'] == pytest.approx(log['wall_s'].max() * 1e3,
                                                abs=1e-3)
    assert 0 < snap['tick_p50_ms'] <= snap['tick_p99_ms'] \
        <= snap['tick_max_ms']
    share = 1 - np.nansum(log['cpu_s']) / np.nansum(log['cpu_wall_s']) \
        - log['wait_s'].sum() / log['wall_s'].sum()
    assert snap['tick_offcpu_share'] == pytest.approx(share, abs=1e-4)
    assert snap['tick_offcpu_share'] < 1


def test_tick_log_since_and_copy(ticked):
    stats, log = ticked['stats'], ticked['log']
    mid = log['t0'][len(log) // 2]
    assert len(stats.tick_log(since=mid)) == len(log) - len(log) // 2
    stats.tick_log()['wall_s'][:] = 0       # a copy: the ring is untouched
    assert stats.tick_log()['wall_s'].sum() == pytest.approx(
        log['wall_s'].sum())


def test_tick_ring_wraps_without_growing():
    from paddle_tpu.inference.decoding import DecodeStats
    stats = DecodeStats()
    ring = stats.TICK_RING
    held = stats._ticks
    for k in range(ring + 1000):
        stats.log_tick(k, float(k), 1e-3, 1e-4, 0.0, 2, 7)
    log = stats.tick_log()
    assert stats._ticks is held and len(held) == ring == len(log)
    assert log['t0'][0] == 1000.0 and log['t0'][-1] == ring + 999.0
    assert np.all(np.diff(log['t0']) == 1)
    assert stats.busy_s == pytest.approx((ring + 1000) * 1e-3)
    assert log['tick'][-1] == ring + 999
    assert len(stats.tick_log(since=ring)) == 1000
    stats.reset()
    assert len(stats.tick_log()) == 0 and stats.snapshot()['tick_max_ms'] == 0


def test_snapshot_looks_at_the_last_window_of_ticks():
    """A poller's snapshot() copies the last `window` rows, across the
    ring's seam too, and never the ring."""
    from paddle_tpu.inference.decoding import DecodeStats
    stats = DecodeStats(window=64)
    for k in range(stats.TICK_RING - 43):
        stats.log_tick(k, float(k), (1 + k % 7) * 1e-3, 0.0, 0.0, 1, 1)
    stats.log_tick(0, 0.0, 0.5, 0.0, 0.0, 1, 1)
    for k in range(62):
        stats.log_tick(k, float(k), 2e-3, 0.0, 0.0, 1, 1)
    with stats._lock:
        last = np.concatenate(stats._last_rows(64))
    assert len(last) == 64 and last['wall_s'][1] == 0.5
    assert len(stats.tick_log()) == stats.TICK_RING     # 20 past the seam
    for column in ('t0', 'wall_s', 'tick'):
        assert np.array_equal(last[column], stats.tick_log()[column][-64:])
    snap = stats.snapshot()
    assert snap['tick_max_ms'] == 500.0 and snap['tick_p50_ms'] == 2.0
    stats.log_tick(0, 0.0, 2e-3, 0.0, 0.0, 1, 1)
    stats.log_tick(0, 0.0, 2e-3, 0.0, 0.0, 1, 1)
    assert stats.snapshot()['tick_max_ms'] == 2.0   # the stop left the window


def test_reset_starts_the_cpu_clocks_readings_anew():
    """No reading of the thread's CPU clock reaches back across a
    reset(): the first one after it spans nothing and is not logged."""
    from paddle_tpu.inference.decoding import DecodeStats
    stats = DecodeStats()
    stats.CPU_EVERY_S = 0
    stats.log_tick(1, 1.0, 1e-3, 0.0, 0.0, 1, 1)
    stats.log_tick(2, 2.0, 1e-3, 0.0, 0.0, 1, 1)
    assert not np.isnan(stats.tick_log()['cpu_s'][1])
    stats.reset()
    stats.log_tick(3, 3.0, 1e-3, 0.0, 0.0, 1, 1)
    stats.log_tick(4, 4.0, 1e-3, 0.0, 0.0, 1, 1)
    log = stats.tick_log()
    assert np.isnan(log['cpu_s'][0]) and log['cpu_wall_s'][1] == 1e-3


# -- the request log: one row for every request that ended ---------------------

_REQUEST_COLUMNS = ['request', 't_submit', 't_admit', 't_last_slice',
                    't_first', 't_end', 'prompt_len', 'prefix_covered',
                    'slices', 'deferred', 'tokens', 'gap_max_s',
                    'admit_tick', 'first_tick', 'outcome']
_TIMES = _REQUEST_COLUMNS[1:6]


def test_request_log_has_one_row_a_request(ticked):
    """Six requests served to their end: six rows, each with the request's
    times in the order they happened, what its prompt took and what it
    was delivered."""
    from paddle_tpu.inference.decoding import DecodeStats
    reqs, snap = ticked['requests'], ticked['snap']
    assert list(reqs.dtype.names) == _REQUEST_COLUMNS
    assert len(reqs) == len(ticked['tokens']) == snap['requests']
    assert len(set(reqs['request'])) == len(reqs)
    assert np.all(reqs['outcome'] == DecodeStats.DONE)
    times = np.stack([reqs[k] for k in _TIMES])
    assert not np.isnan(times).any() and np.all(np.diff(times, axis=0) >= 0)
    assert reqs['t_submit'].min() >= ticked['t_first']
    # the rows are in the order the requests ENDED; `request` is the order
    # they were submitted in
    assert np.all(np.diff(reqs['t_end']) >= 0)
    by_seq = reqs[np.argsort(reqs['request'])]
    assert by_seq['prompt_len'].tolist() == [len(p) for p in ticked['prompts']]
    assert by_seq['tokens'].tolist() == [len(t) for t in ticked['tokens']]
    assert np.all(reqs['prefix_covered'] == 0)
    assert reqs['slices'].sum() == snap['chunk_slices']
    assert reqs['deferred'].sum() == snap['slices_deferred']
    assert by_seq['slices'].tolist() == [
        len(alone_slices(CHUNKS, len(p))) for p in ticked['prompts']]
    assert np.all(reqs['gap_max_s'] > 0)
    assert np.all(reqs['gap_max_s'] <= reqs['t_end'] - reqs['t_first'] + 1e-9)


def test_request_log_joins_the_tick_log_on_tick_numbers(ticked):
    """`admit_tick` and `first_tick` are `tick`s of the tick log's rows:
    the tick that admitted the request holds its `t_admit`, the one that
    delivered its first token its `t_first` and counts it in `rows`."""
    reqs, log = ticked['requests'], ticked['log']
    rows = {int(r['tick']): r for r in log}
    assert len(rows) == len(log)
    for req in reqs:
        admit, first = rows[int(req['admit_tick'])], \
            rows[int(req['first_tick'])]
        assert admit['t0'] <= req['t_admit'] <= admit['t0'] + admit['wall_s']
        assert first['t0'] <= req['t_first'] <= first['t0'] + first['wall_s']
        assert first['rows'] - first['emit_rows'] >= 1
        assert first['wait_slice_s'] > 0
        assert req['first_tick'] > req['admit_tick']
        # its last slice went in the tick before the one that read it
        assert req['t_last_slice'] < first['t0']


def test_request_log_counts_each_requests_slices_and_waits(budgeted):
    """Several prompts of several slices admitted at once: `slices` is
    what each takes alone, `deferred` the ticks its due slices waited —
    they add up to chunk_slices and slices_deferred."""
    reqs, snap = budgeted['requests'], budgeted['snap']
    by_seq = reqs[np.argsort(reqs['request'])]
    assert by_seq['slices'].tolist() == [
        len(alone_slices(budgeted['chunks'], len(p)))
        for p in budgeted['prompts']]
    assert reqs['slices'].sum() == snap['chunk_slices']
    assert reqs['deferred'].sum() == snap['slices_deferred'] > 0
    waited = {}
    for t in budgeted['ticks']:
        went = {seq for seq, _, _ in t['went']}
        for seq, _, _ in t['due']:
            waited[seq] = waited.get(seq, 0) + (seq not in went)
    assert by_seq['deferred'].tolist() == [waited[seq] for seq in
                                           sorted(waited)]
    assert by_seq['tokens'].tolist() == budgeted['news']
    # the oldest never waits; whoever waited got its first token later
    assert by_seq['deferred'][0] == 0


def _end_done(pred):
    return [pred.submit(_prompts(61, 1)[0], max_new_tokens=3)], ['DONE']


def _end_cancelled_waiting(pred):
    gate = _gated(pred)
    stream = pred.submit(_prompts(62, 1)[0], max_new_tokens=3)
    stream.cancel()
    gate.set()
    return [stream], ['CANCELLED']


def _end_cancelled_decoding(pred):
    stream, head, gate = _held_behind(pred, _prompts(17, 1)[0], 57)
    stream.cancel()
    gate.set()
    return [stream], ['CANCELLED']


def _end_expired_waiting(pred):
    return [pred.submit(_prompts(63, 1)[0], max_new_tokens=3,
                        deadline_ms=0.0)], ['EXPIRED']


def _end_expired_decoding(pred):
    def past_due(pred):
        for req in pred._active_requests():
            if req.produced >= 3:
                req.deadline = 0.0
    _gated(pred, before=past_due).set()
    return [pred.submit(_prompts(17, 1)[0], max_new_tokens=57,
                        deadline_ms=3.6e6)], ['EXPIRED']


def _end_drained(pred):
    """One decoding, one found waiting by the tick that drains."""
    import threading
    first, head, gate = _held_behind(pred, _prompts(17, 1)[0], 6)
    waiting = pred.submit(_prompts(64, 1)[0], max_new_tokens=3)
    drain = threading.Thread(target=pred.drain, args=(60,))
    drain.start()
    while not pred._draining:
        time.sleep(0.001)
    gate.set()
    drain.join(60)
    assert not drain.is_alive()
    return [first, waiting], ['DONE', 'SHED']


def _end_failed(pred):
    """_fail_all: the chunk programs break under two admitting requests."""
    def boom(*args, **kw):
        raise RuntimeError('chunk program broke')
    for m in list(pred._chunk_mods.values()) + [pred._row_mod]:
        m.call = boom
    return [pred.submit(p, max_new_tokens=3)
            for p in _prompts(65, 2)], ['FAILED', 'FAILED']


def _end_closed(pred):
    """close(): one decoding, one still queued behind a held scheduler."""
    import threading
    first, head, gate = _held_behind(pred, _prompts(17, 1)[0], 57)
    queued = pred.submit(_prompts(66, 1)[0], max_new_tokens=3)
    close = threading.Thread(target=pred.close)
    close.start()
    while not pred._closed:
        time.sleep(0.001)
    gate.set()
    close.join(60)
    assert not close.is_alive()
    return [first, queued], ['FAILED', 'FAILED']


@pytest.mark.parametrize('how', [
    _end_done, _end_cancelled_waiting, _end_cancelled_decoding,
    _end_expired_waiting, _end_expired_decoding, _end_drained, _end_failed,
    _end_closed], ids=lambda f: f.__name__[5:])
def test_request_log_has_one_row_whatever_the_end(artifact, how):
    """Done, cancelled, expired, shed by a drain, failed by _fail_all or
    by close(): exactly one row a request, its outcome named, its times
    NaN from where it never got and in order up to there."""
    from paddle_tpu.inference.decoding import DecodeStats
    with DecodingPredictor(artifact) as pred:
        streams, want = how(pred)
        for s in streams:
            s.exception(120)
        assert all(s.done() for s in streams)
    reqs = pred.stats.request_log()
    assert len(reqs) == len(streams) == len(set(reqs['request']))
    by_seq = reqs[np.argsort(reqs['request'])]
    assert by_seq['outcome'].tolist() == [getattr(DecodeStats, w)
                                          for w in want]
    for req, stream in zip(by_seq, streams):
        times = np.array([req[k] for k in _TIMES])
        got = ~np.isnan(times)
        assert got[0] and got[-1] and np.all(np.diff(times[got]) >= 0)
        # never admitted: no slice, no token, no tick; admitted: a tick
        if np.isnan(req['t_admit']):
            assert np.isnan(req['admit_tick']) and req['slices'] == 0
            assert np.isnan(req['t_last_slice'])
        else:
            assert req['admit_tick'] >= 1
        assert np.isnan(req['t_first']) == np.isnan(req['first_tick']) \
            == (req['tokens'] == 0)
        if req['outcome'] == DecodeStats.DONE:
            assert req['tokens'] == len(stream.result(0))
    if how is _end_cancelled_decoding:
        assert reqs['tokens'][0] >= 1 and reqs['gap_max_s'][0] >= 0
    if how is _end_expired_decoding:
        assert reqs['tokens'][0] == 3 and reqs['gap_max_s'][0] > 0


def test_gap_max_is_the_longest_gap_the_stream_was_handed(artifact):
    """`gap_max_s` against stamps taken where each token enters the
    request's stream (the consumer's side of the scheduler), with one
    tick held for 60 ms in the middle of the answer: within a
    millisecond."""
    stamps, held = [], []

    def hold_one_tick(pred):
        if not held and any(r.produced == 3
                            for r in pred._active_requests()):
            held.append(time.sleep(0.06))
    with DecodingPredictor(artifact) as pred:
        gate = _gated(pred, before=hold_one_tick)
        stream = pred.submit(_prompts(17, 1)[0], max_new_tokens=8)
        push = stream._push

        def stamped(tok):
            stamps.append(time.perf_counter())
            push(tok)
        stream._push = stamped
        gate.set()
        assert len(stream.result(120)) == 8 == len(stamps)
    row, = pred.stats.request_log()
    longest = np.diff(stamps).max()
    assert longest >= 0.06
    assert row['gap_max_s'] == pytest.approx(longest, abs=1e-3)
    assert row['t_first'] == pytest.approx(stamps[0], abs=1e-3)
    assert row['t_end'] == pytest.approx(stamps[-1], abs=1e-3)


def test_reset_empties_both_rings(artifact):
    with DecodingPredictor(artifact) as pred:
        pred.generate(_prompts(67, 1)[0], max_new_tokens=3)
        assert pred.drain(60)
        assert len(pred.stats.request_log()) == 1
        assert len(pred.stats.tick_log()) > 1
        pred.stats.reset()
        assert len(pred.stats.request_log()) == 0
        assert len(pred.stats.tick_log()) == 0
        snap = pred.stats.snapshot()
    assert snap['emit_gap_p99_ms'] == 0 == snap['ttft_prefill_p99_ms']


def test_request_ring_wraps_without_growing():
    from paddle_tpu.inference.decoding import DecodeStats
    stats = DecodeStats()
    ring, held = stats.REQUEST_RING, stats._requests
    row = np.zeros((), stats.REQUEST_ROW)
    for k in range(ring + 100):
        row['request'], row['t_submit'] = k, float(k)
        stats.log_request(row.item(), requests=1)
    log = stats.request_log()
    assert stats._requests is held and len(held) == ring == len(log)
    assert log['request'][0] == 100 and log['request'][-1] == ring + 99
    assert np.all(np.diff(log['request']) == 1)
    assert stats.requests == ring + 100      # the counters ride the hold
    assert len(stats.request_log(since=ring)) == 100
    stats.request_log()['request'][:] = -1      # a copy
    assert stats.request_log()['request'][0] == 100


def test_snapshot_reads_the_gaps_and_the_first_tokens_parts(ticked):
    """emit_gap_*: the gaps between adjacent ticks' deliveries, each
    counted once a row the closing tick delivered to; ttft_*: the three
    parts of the requests' time to their first token, which add up."""
    log, reqs, snap = ticked['log'], ticked['requests'], ticked['snap']
    t, rows = log['emit_t'], log['emit_rows']
    both = ~np.isnan(t[1:]) & ~np.isnan(t[:-1])
    seen = np.repeat(np.diff(t)[both], rows[1:][both].astype(int)) * 1e3
    for q in (50, 99):
        assert snap['emit_gap_p%d_ms' % q] == pytest.approx(
            np.percentile(seen, q, method='inverted_cdf'), abs=1e-3)
    assert 0 < snap['emit_gap_p50_ms'] <= snap['emit_gap_p99_ms']
    parts = {'queue': reqs['t_admit'] - reqs['t_submit'],
             'prefill': reqs['t_last_slice'] - reqs['t_admit'],
             'read': reqs['t_first'] - reqs['t_last_slice']}
    for name, seconds in parts.items():
        for q in (50, 99):
            assert snap['ttft_%s_p%d_ms' % (name, q)] == pytest.approx(
                np.percentile(seconds, q) * 1e3, abs=1e-3)
    np.testing.assert_allclose(sum(parts.values()),
                               reqs['t_first'] - reqs['t_submit'], atol=1e-9)


def test_the_collector_hook_is_installed_once(artifact):
    """However many predictors a process builds, gc.callbacks holds ONE
    hook; the seconds it counts only grow."""
    import gc
    from paddle_tpu.inference import serve
    before = serve.gc_seconds()
    with DecodingPredictor(artifact), DecodingPredictor(artifact):
        assert gc.callbacks.count(serve._gc_event) == 1
        gc.collect()
    assert gc.callbacks.count(serve._gc_event) == 1
    assert serve.gc_seconds() > before
