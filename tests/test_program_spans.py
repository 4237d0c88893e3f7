"""The program's own spans (profiler.span over jax.profiler.TraceAnnotation)
in a jax profiler trace that somebody else started: the decode scheduler's
and the executor's, their nesting, the stats that join one request's spans,
what a span costs with no trace running, and the device-side names (Fluid
op types in op_name metadata, stable names on the jitted programs).

On the cpu backend, read back with jax.profiler.ProfileData — the same way
benchmark/layer_metrics/_spans.py reads a chip trace."""
import gc
import glob
import os
import statistics
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.inference import DecodingPredictor, export_decode

VOCAB, SLOTS, CACHE = 41, 4, 64
_TOL_NS = 1000      # an event's end is start + duration, both rounded


class _Trace(object):
    """Host spans of one .xplane.pb: (name, start, end, thread, stats).
    Every python thread's line has the process's name: a thread is its
    line's name and number."""

    def __init__(self, trace_dir):
        from jax.profiler import ProfileData
        path = max(glob.glob(os.path.join(trace_dir, 'plugins', 'profile',
                                          '*', '*.xplane.pb')),
                   key=os.path.getmtime)
        self.spans = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith('/host:CPU'):
                continue
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if '/' in e.name and e.name.split('/')[0] in (
                            'decode', 'exe', 'load', 'compile', 'pass',
                            'py'):
                        self.spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns,
                             '%s#%d' % (line.name, k), dict(e.stats)))
        self.spans.sort(key=lambda s: s[1])

    def named(self, name):
        return [s for s in self.spans if s[0] == name]

    def cpu_slack_us(self):
        """How far a span's cpu_us may pass its wall time: the two clocks
        differ by a few microseconds, and a thread's CPU clock may move in
        steps (10 ms under a sandboxed kernel) — the smallest movement any
        span of the trace saw is at least one step."""
        moved = [s[4]['cpu_us'] for s in self.spans
                 if s[4].get('cpu_us', 0) > 0]
        return max(20.0, min(moved, default=0.0))

    def parent_of(self, span, names):
        """The innermost span named one of `names` that holds `span` on
        its own thread, or None."""
        _, s, e, thread, _ = span
        holders = [p for p in self.spans
                   if p[0] in names and p[3] == thread and p is not span
                   and p[1] <= s + _TOL_NS and e <= p[2] + _TOL_NS]
        return max(holders, key=lambda p: p[1]) if holders else None

    def inside(self, holder, name, program=''):
        """The spans called `name` (whose 'program' stat starts with
        `program`) that `holder` holds on its own thread."""
        return [s for s in self.named(name)
                if s[3] == holder[3] and holder[1] <= s[1] + _TOL_NS
                and s[2] <= holder[2] + _TOL_NS
                and s[4].get('program', '').startswith(program)]


def _traced(tmp, body):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        out = body()
    finally:
        jax.profiler.stop_trace()
    return _Trace(str(tmp)), out


# -- (a) the decode scheduler ------------------------------------------------

@pytest.fixture(scope='module')
def decode_art(tmp_path_factory):
    """A small decode artifact, built as
    tests/test_kv_blocks.py builds its own."""
    from models.transformer import build_decode_spec
    art = str(tmp_path_factory.mktemp('spans') / 'art')
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(
            vocab=VOCAB, d_model=16, n_head=2, n_layer=2, d_ff=32,
            max_slots=SLOTS, max_cache_len=CACHE, eos_id=1,
            chunk_sizes=(4, 8), block_size=4)
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'])
        export_decode(spec, art, scope=scope)
    return art


@pytest.fixture(scope='module')
def decode_trace(decode_art, tmp_path_factory):
    """Three requests (one carrying a gateway request id, one long enough
    for two prefill slices) served inside a trace the TEST started — the
    program is told nothing."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, VOCAB, n) for n in (3, 11, 6)]

    def serve():
        with DecodingPredictor(decode_art) as pred:
            # one collection on the scheduler's thread, inside a tick
            admit, once = pred._admit, [gc.collect]

            def admit_after_a_collection(waiting):
                while once:
                    once.pop()()
                return admit(waiting)
            pred._admit = admit_after_a_collection
            streams = [pred.submit(p, max_new_tokens=4,
                                   request_id='gw-7' if i == 1 else None)
                       for i, p in enumerate(prompts)]
            tokens = [list(s.result(120)) for s in streams]
            assert pred.drain(60)       # the last tick's row is written
            return tokens, pred.stats.request_log(), pred.stats.tick_log()

    trace, (tokens, trace.requests, trace.ticks) = _traced(
        tmp_path_factory.mktemp('decode_trace'), serve)
    assert all(tokens)
    return trace


_DECODE_SPANS = ('decode/submit', 'decode/tick', 'decode/expire',
                 'decode/admit', 'decode/admit_request',
                 'decode/prefill_slice', 'decode/step', 'decode/build_feed',
                 'decode/dispatch', 'decode/device_wait', 'decode/d2h',
                 'decode/advance', 'decode/first_token', 'decode/finish',
                 'decode/publish_prefix', 'py/gc',
                 'load/read', 'load/weights', 'load/reset_state')


@pytest.mark.parametrize('name', _DECODE_SPANS)
def test_decode_span_is_in_the_trace(decode_trace, name):
    assert decode_trace.named(name), \
        'no %s span; have %s' % (name, sorted({s[0] for s in
                                               decode_trace.spans}))


@pytest.mark.parametrize('child,parents', [
    ('decode/expire', ('decode/tick',)),
    ('decode/admit', ('decode/tick',)),
    ('decode/admit_request', ('decode/admit',)),
    ('decode/prefill_slice', ('decode/tick',)),
    ('decode/step', ('decode/tick',)),
    ('decode/build_feed', ('decode/step',)),
    ('decode/advance', ('decode/step',)),
    # the slices several requests have due in one tick ride ONE call of
    # the row program, which follows their spans: in the tick's own
    ('decode/dispatch', ('decode/step', 'decode/prefill_slice',
                         'decode/tick')),
    # a slice is dispatched inside its span and never waited for there;
    # a prompt's last slice is read at the end of the NEXT tick, in no
    # span but the tick's
    ('decode/device_wait', ('decode/step', 'decode/tick')),
    ('decode/d2h', ('decode/step', 'decode/tick')),
    ('decode/first_token', ('decode/tick',)),
    ('decode/finish', ('decode/advance', 'decode/first_token')),
    # a prompt's full blocks are published where its last slice is read
    ('decode/publish_prefix', ('decode/tick',)),
])
def test_decode_children_lie_inside_their_parents(decode_trace, child,
                                                  parents):
    spans = [s for s in decode_trace.named(child)
             # the constructor's state reset dispatches outside any tick
             if s[4].get('program') != 'zeros']
    assert spans
    for span in spans:
        assert decode_trace.parent_of(span, parents) is not None, \
            '%s at %d has no %s around it on thread %s' % (
                child, span[1], ' / '.join(parents), span[3])


def test_one_request_stat_joins_a_requests_spans(decode_trace):
    submits = decode_trace.named('decode/submit')
    assert len(submits) == 3
    seqs = [s[4]['request'] for s in submits]
    assert len(set(seqs)) == 3
    for seq, plen in zip(seqs, (3, 11, 6)):
        mine = {name: [s for s in decode_trace.named(name)
                       if s[4].get('request') == seq]
                for name in ('decode/submit', 'decode/admit_request',
                             'decode/prefill_slice', 'decode/first_token',
                             'decode/finish')}
        assert all(len(v) >= 1 for v in mine.values()), mine
        assert mine['decode/submit'][0][4]['prompt_len'] == plen
        admit = mine['decode/admit_request'][0][4]
        assert admit['prompt_len'] == plen and admit['waited_us'] >= 0
        assert admit['prefix_covered'] == 0
        # chunks are 4 and 8 wide: the 11-token prompt takes two slices
        slices = [s[4] for s in mine['decode/prefill_slice']]
        assert sum(s['take'] for s in slices) == plen
        assert len(slices) == (2 if plen == 11 else 1)
        assert [s['start'] for s in slices] == \
            [0, 8][:len(slices)]
        # submit -> admit -> first token -> finish, in that order
        order = [mine[n][0][1] for n in (
            'decode/submit', 'decode/admit_request', 'decode/first_token',
            'decode/finish')]
        assert order == sorted(order)
    # the caller's trace id rides on the spans of the request that had one
    tagged = [s for s in decode_trace.spans if 'request_id' in s[4]]
    assert {s[4]['request_id'] for s in tagged} == {'gw-7'}
    assert {s[0] for s in tagged} == {'decode/first_token', 'decode/finish'}
    assert {s[4]['request'] for s in tagged} == {seqs[1]}


def test_the_request_logs_rows_join_the_spans_and_the_tick_log(decode_trace):
    """A request's row of the request log carries the `request` stat of
    its spans, and the `tick` stats of the ticks that admitted it and
    delivered its first token — the numbers the tick log's rows carry."""
    reqs = decode_trace.requests
    submits = {s[4]['request']: s for s in decode_trace.named('decode/submit')}
    assert sorted(reqs['request']) == sorted(submits) and len(reqs) == 3
    ticks = set(decode_trace.ticks['tick'])
    for req in reqs:
        seq = int(req['request'])
        mine = {name: [s for s in decode_trace.named(name)
                       if s[4].get('request') == seq]
                for name in ('decode/admit_request', 'decode/prefill_slice',
                             'decode/first_token', 'decode/finish')}
        assert req['prompt_len'] == submits[seq][4]['prompt_len']
        assert req['slices'] == len(mine['decode/prefill_slice'])
        admit, = mine['decode/admit_request']
        assert admit[4]['waited_us'] == int(
            (req['t_admit'] - req['t_submit']) * 1e6)
        first, = mine['decode/first_token']
        for span, column in ((admit, 'admit_tick'), (first, 'first_tick')):
            tick = decode_trace.parent_of(span, ('decode/tick',))
            assert tick[4]['tick'] == req[column] and req[column] in ticks
        assert mine['decode/finish'][0][4]['outcome'] == 'done'
        assert req['outcome'] == 0 and req['tokens'] == 4


def test_advance_says_the_gap_since_the_delivery_before(decode_trace):
    """While a trace runs the step read's 'decode/advance' carries
    `gap_us`, this delivery's stamp minus the previous one's: the
    difference of the tick log's `emit_t` between the two ticks."""
    emit_t = {int(r['tick']): r['emit_t'] for r in decode_trace.ticks}
    gaps, stamped = [], []
    for adv in decode_trace.named('decode/advance'):
        tick = decode_trace.parent_of(adv, ('decode/tick',))[4]['tick']
        stamped.append(emit_t[tick])
        gaps.append(adv[4].get('gap_us'))
    assert len(gaps) > 3 and gaps[0] is None and None not in gaps[1:]
    assert not np.isnan(stamped).any()
    assert gaps[1:] == [int(d * 1e6) for d in np.diff(stamped)]


def test_step_d2h_bytes_is_the_ids(decode_trace):
    """A greedy step copies its [max_slots] int32 ids and nothing else;
    every d2h span says which fetch it moved."""
    step = [s for s in decode_trace.named('decode/d2h')
            if s[4]['program'] == 'step']
    assert step
    assert {s[4]['bytes'] for s in step} == {SLOTS * 4}
    assert {s[4]['fetch'] for s in decode_trace.named('decode/d2h')} \
        == {'ids'}
    # a prefill slice's copy is its one id, the row program's its four
    assert {(s[4]['program'], s[4]['bytes'])
            for s in decode_trace.named('decode/d2h')
            if s[4]['program'].startswith('chunk_')} \
        == {('chunk_4', 4), ('chunk_8x4', 16)}
    programs = {s[4]['program'] for s in decode_trace.named('decode/dispatch')}
    assert {'step', 'chunk_4', 'chunk_8x4', 'zeros'} <= programs
    ticks = [s[4]['tick'] for s in decode_trace.named('decode/tick')]
    assert ticks == sorted(ticks) and len(set(ticks)) == len(ticks)


def test_a_slice_is_dispatched_and_only_a_prompts_last_is_read(decode_trace):
    """Inside its span a slice is a dispatch and nothing else — no wait,
    no copy — or, where the tick has several slices of the largest bucket
    due and they ride one call of the row program, its bookkeeping alone:
    the short prompt's slice (the small bucket's) is a call of its own
    inside its span, the two others' first slices are one call of two
    rows behind their spans, the long prompt's second slice a call of its
    own again. `last` says whether the tick will read it, and the reads
    of the trace are exactly the calls that held a prompt's last slice."""
    slices = decode_trace.named('decode/prefill_slice')
    assert {s[4]['last'] for s in slices} == {0, 1}
    held = []
    for sl in slices:
        held.append(len(decode_trace.inside(sl, 'decode/dispatch')))
        assert not decode_trace.inside(sl, 'decode/device_wait')
        assert not decode_trace.inside(sl, 'decode/d2h')
    assert held == [1, 0, 0, 1]
    calls = [s[4] for s in decode_trace.named('decode/dispatch')
             if s[4]['program'].startswith('chunk_')]
    assert [(c['program'], c['rows']) for c in calls] \
        == [('chunk_4', 1), ('chunk_8x4', 2), ('chunk_4', 1)]
    reads = [s for s in decode_trace.named('decode/d2h')
             if s[4]['program'].startswith('chunk_')]
    assert len(reads) == sum(s[4]['last'] for s in slices) == 3
    assert len(decode_trace.named('decode/first_token')) == 3


def test_the_step_goes_first_and_the_last_tick_is_read_behind_it(
        decode_trace):
    """In a tick the next step is dispatched first, then what the
    PREVIOUS tick dispatched is read — dispatch(step k+1) ends before
    d2h(step k) begins, and the step's tokens are out (decode/advance
    ends) before a slice's result is touched — and only then the tick's
    own slices are dispatched, behind every read."""
    ahead = slices = 0
    for tick in decode_trace.named('decode/tick'):
        step = decode_trace.inside(tick, 'decode/dispatch', 'step')
        chunks = decode_trace.inside(tick, 'decode/dispatch', 'chunk_')
        waits = decode_trace.inside(tick, 'decode/device_wait', 'step')
        copies = decode_trace.inside(tick, 'decode/d2h', 'step')
        assert len(step) <= 1 and len(waits) == len(copies) <= 1
        if step and chunks:
            assert step[0][2] <= chunks[0][1] + _TOL_NS
        if step and copies:
            ahead += 1
            assert step[0][2] <= waits[0][1] + _TOL_NS
            assert step[0][2] <= copies[0][1] + _TOL_NS
        firsts = decode_trace.inside(tick, 'decode/device_wait', 'chunk_')
        advance = decode_trace.inside(tick, 'decode/advance')
        for read in firsts:
            assert all(a[2] <= read[1] + _TOL_NS for a in advance)
        reads = decode_trace.inside(tick, 'decode/d2h')
        if chunks and reads:
            slices += 1
            assert max(r[2] for r in reads) <= chunks[0][1] + _TOL_NS
    assert ahead >= 1 and slices >= 1


def test_the_dispatch_half_of_a_step_says_whether_it_ran_ahead(decode_trace):
    """decode/step opens twice for one step: the dispatch half carries
    `ahead` (1: the previous tick's programs were unread when it was
    dispatched), the read half, a tick later, does not. Greedy traffic
    runs every step ahead."""
    halves = decode_trace.named('decode/step')
    dispatch = [s for s in halves if 'ahead' in s[4]]
    read = [s for s in halves if 'ahead' not in s[4] and s[4]['active']]
    assert len(dispatch) == len(read) >= 3
    assert {s[4]['ahead'] for s in dispatch} == {1}
    for d, r in zip(dispatch, read):
        assert d[4]['active'] == r[4]['active']
        assert decode_trace.inside(d, 'decode/dispatch', 'step')
        assert not decode_trace.inside(d, 'decode/d2h')
        assert decode_trace.inside(r, 'decode/d2h', 'step')
        # the read half lies in a LATER tick than its dispatch half
        assert decode_trace.parent_of(d, ('decode/tick',))[4]['tick'] \
            < decode_trace.parent_of(r, ('decode/tick',))[4]['tick']


def test_the_collector_is_a_span_on_the_thread_that_collects(decode_trace):
    """The one forced collection ran in the scheduler's _admit: its py/gc
    span (generation 2) lies inside that tick's decode/admit, on the
    scheduler's thread."""
    full = [s for s in decode_trace.named('py/gc')
            if s[4]['generation'] == 2]
    assert full
    held = [decode_trace.parent_of(s, ('decode/admit',)) for s in full]
    held = [h for h in held if h is not None]
    assert len(held) == 1
    assert decode_trace.parent_of(held[0], ('decode/tick',)) is not None


def test_publish_prefix_says_how_many_blocks(decode_trace):
    """One span a prompt, between the read of its last slice and its first
    token: the prompt's full blocks (pages of 4 rows; prompts of 3, 11
    and 6 tokens)."""
    spans = decode_trace.named('decode/publish_prefix')
    assert sorted(s[4]['blocks'] for s in spans) == [0, 1, 2]
    for s in spans:
        assert decode_trace.parent_of(
            s, ('decode/first_token', 'decode/step')) is None


def test_a_dispatch_says_what_it_handed_over(decode_trace):
    """decode/dispatch carries the host arrays the call was given: a step
    its tokens, positions and table rows, a slice five feeds, the state's
    birth one."""
    by_program = {}
    for s in decode_trace.named('decode/dispatch'):
        by_program.setdefault(s[4]['program'], set()).add(
            (s[4]['feeds'], s[4]['feed_bytes']))
    (step,) = by_program['step']
    assert step[0] == 3 and step[1] > SLOTS * (CACHE // 4) * 4
    assert by_program['zeros'] == {(1, 4)}
    assert {n for n, _ in by_program['chunk_4']} == {5}
    assert {n for n, _ in by_program['chunk_8x4']} == {5}


@pytest.mark.parametrize('name', ['decode/tick', 'decode/dispatch',
                                  'decode/device_wait', 'decode/advance',
                                  'decode/submit', 'load/weights', 'py/gc'])
def test_every_span_carries_its_threads_cpu_time(decode_trace, name):
    """cpu_us: the CPU time the span's thread used inside it, never more
    than the span lasted (two clocks: a few microseconds of slack, or one
    step of a CPU clock that moves in steps), and a holder's is at least
    its children's."""
    spans = decode_trace.named(name)
    assert spans
    slack = decode_trace.cpu_slack_us()
    for s in spans:
        assert 0 <= s[4]['cpu_us'] <= (s[2] - s[1]) / 1e3 + slack, s
    if name == 'decode/tick':
        for tick in spans:
            inside = sum(s[4]['cpu_us'] for s in decode_trace.spans
                         if s is not tick and s[3] == tick[3]
                         and decode_trace.parent_of(s, _HOLDERS) is tick)
            assert inside <= tick[4]['cpu_us'] + slack


_HOLDERS = ('decode/tick', 'decode/step', 'decode/prefill_slice',
            'decode/admit', 'decode/advance', 'decode/first_token',
            'decode/build_feed', 'decode/admit_request')


@pytest.mark.parametrize('sub,name', [
    ('decode_step', 'decode_step'), ('prefill_chunk_00004', 'prefill_chunk_4'),
    ('prefill_chunk_00008', 'prefill_chunk_8'),
    ('decode_zeros', 'decode_zeros'),
    ('decode_blockcopy', 'decode_blockcopy')])
def test_exported_decode_programs_have_stable_names(decode_art, sub, name):
    """What 'XLA Modules' prints as jit_<name> for an AOT-loaded program."""
    from jax import export as jexport
    from paddle_tpu.inference import serve
    with open(os.path.join(decode_art, sub, serve._MODULE), 'rb') as f:
        exp = jexport.deserialize(f.read())
    assert exp.fun_name == name
    assert serve._named_call(exp).__name__ == name


# -- (b) the executor --------------------------------------------------------

def test_executor_run_spans(tmp_path):
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.fc(x, size=2, act='relu')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = {'x': np.ones((2, 4), np.float32)}

    def three():
        for _ in range(3):
            exe.run(feed=feed, fetch_list=[y])

    trace, _ = _traced(tmp_path, three)
    runs = trace.named('exe/run')
    assert len(runs) == 3
    assert [r[4]['step'] for r in runs] == [0, 1, 2]
    assert len({r[4]['program'] for r in runs}) == 1
    for i, run in enumerate(runs):
        inside = [s[0] for s in trace.spans
                  if s[0].startswith('exe/') and s is not run
                  and trace.parent_of(s, ('exe/run',)) is run]
        want = ['exe/feed', 'exe/prepare', 'exe/rng', 'exe/dispatch',
                'exe/finish']
        if i == 0:          # the cache miss, and only it, builds
            want.insert(2, 'exe/build')
        assert inside == want
    assert trace.named('exe/feed')[0][4]['n'] == 1


# -- (c) what it costs with no trace running ----------------------------------

def test_inactive_spans_record_nothing_and_cost_microseconds():
    assert not profiler.is_profiling()
    profiler.reset_profiler()
    for _ in range(10000):
        with profiler.record_event('pass/none'):
            pass
    assert profiler._events == []
    costs = []
    for i in range(2000):
        t0 = time.perf_counter()
        with profiler.span('decode/none', request=i, program='step'):
            pass
        costs.append(time.perf_counter() - t0)
    assert statistics.median(costs) < 25e-6


# -- (c') working or waiting: cpu_us while a trace runs -----------------------

_SPUN_S = 0.1       # the CPU time the spinning span uses, by its own clock


@pytest.fixture(scope='module')
def sleep_and_spin(tmp_path_factory):
    """One span that sleeps 0.1 s, one that spins until its thread has
    used 0.1 s of CPU — however long a loaded machine takes to give it
    that much."""
    def body():
        with profiler.span('pass/sleeps'):
            time.sleep(0.1)
        with profiler.span('pass/spins'):
            t0 = time.thread_time()
            while time.thread_time() - t0 < _SPUN_S:
                pass
    return _traced(tmp_path_factory.mktemp('cpu_us'), body)[0]


@pytest.mark.parametrize('name,cpu_s', [
    ('pass/sleeps', (0.0, 0.02)),           # waiting: hardly any CPU time
    ('pass/spins', (_SPUN_S, 1.1 * _SPUN_S)),   # working: what it spun for
])
def test_cpu_us_tells_working_from_waiting(sleep_and_spin, name, cpu_s):
    (span,) = sleep_and_spin.named(name)
    wall_us = (span[2] - span[1]) / 1e3
    assert wall_us >= 100e3
    slack = sleep_and_spin.cpu_slack_us()
    assert cpu_s[0] * 1e6 - slack <= span[4]['cpu_us'] \
        <= min(cpu_s[1] * 1e6, wall_us) + slack, span


def test_a_span_outside_a_trace_takes_no_cpu_clock():
    """No trace running: the span reads no clock and adds no stat."""
    with profiler.span('pass/none') as sp:
        pass
    assert sp._cpu0 is None


# -- (d) the device side: op types in op_name, names on the programs ---------

def _lowered_step(train):
    import jax
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    h = fluid.layers.fc(x, size=3, act='relu')
    fetch = h
    if train:
        fetch = fluid.layers.mean(h)
        fluid.optimizer.SGD(0.1).minimize(fetch)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    prog = fluid.default_main_program()
    from paddle_tpu import executor
    state, _, out_names = exe._gather_state(prog, fluid.global_scope())
    step = exe._trace_step_fn(prog, (fetch.name,), out_names, None)
    step.__name__ = executor._step_name(prog)
    rng = exe._host_rng(1, 'threefry2x32', 0)
    return jax.jit(step).lower(state, {'x': np.ones((2, 4), np.float32)},
                               rng)


@pytest.mark.parametrize('train,module,scopes', [
    (False, 'jit_program_step', ('/mul/', '/relu/')),
    (True, 'jit_train_step', ('/mul/', '/relu/', '/mean/', '/mul_grad/',
                              '/sgd/')),
])
def test_lowered_text_names_the_fluid_ops(train, module, scopes):
    low = _lowered_step(train)
    text = low.as_text(debug_info=True)
    assert '@%s' % module in text
    for scope in scopes:
        assert scope in text, 'no %s scope in the op_name metadata' % scope
