"""Qwen3-Next decode serving on the cpu at a toy size (4 layers = one period
L L L F, hidden 64, full attention 4 / 2 heads of 16 with 4 rotary channels,
Gated DeltaNet 2 key / 4 value heads of 8, convolution width 4, 16 routed
experts top-4 of which 4 are held from offset 4, a gated shared expert,
vocab 128), seeded weights: prefill in slices through the CHUNKED rule with
its state and convolution tail carried from slice to slice, then decode
through cache and state, against the plain reference's full-forward LOGITS
(benchmark/reference/qwen3_next.py: the recurrence token by token from a
zero state); the chunked op against the recurrence alone; the convolution's
tail across a boundary; the controls that have to fail; who may touch a
slot's state when (other slots stepping between a prompt's slices, a slot's
second tenant, a step dispatched ahead of an eos); what is refused by name;
what the artifact says of the states; the eight shares against the uncut
layer."""
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, decoding, export_decode
from paddle_tpu.ops import linear_attention_ops as lao
from paddle_tpu.ops import llm_ops
from paddle_tpu.testing.decode_logits import served_logits
from benchmark.reference import qwen3_next as ref
from models.qwen3_next import (FULL, LINEAR, build_decode_spec,
                               decay_log_range, layer_types)

from decode_feed_check import (fits_first, gated, watch_feed,
                               watch_slices)

TOY = dict(vocab=128, d_model=64, n_head=4, n_kv_head=2, d_head=16,
           rotary_dim=4, n_layer=4, lin_key_heads=2, lin_value_heads=4,
           lin_dk=8, lin_dv=8, n_expert=16, n_held=4, expert_offset=4,
           d_expert=32, top_k=4, d_shared=32, max_slots=8, max_cache_len=128,
           block_size=8, chunk_sizes=(8, 16))
REF = dict(n_head=4, n_kv_head=2, rotary_dim=4, n_layer=4, hk=2, hv=4, dk=8,
           dv=8, top_k=4, expert_offset=4)
# one slice; two slices, the last short (16 + 5); three (16 + 16 + 8 of 8);
# five with a short last one (4 x 16 + 6 of 8)
PROMPTS = (5, 21, 40, 70)
N_NEW = 33                  # the prompt's last slice, then 32 decode steps
LINEAR_LAYERS = [i for i, t in enumerate(layer_types(4)) if t == LINEAR]


def _export(tmp, dtype='float32', seed=3, **over):
    art = str(tmp)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(weights_dtype=dtype, kv_cache_dtype=dtype,
                                 **dict(TOY, **over))
        spec['startup'].random_seed = seed
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        weights = {n: np.asarray(scope.get(n))
                   for n in scope.local_var_names()
                   if n not in spec['cache_vars']}
        export_decode(spec, art, scope=scope, precompile=False)
    return art, weights


def _prompts(lens=PROMPTS, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, TOY['vocab'], n) for n in lens]


@pytest.fixture(scope='module')
def served(tmp_path_factory):
    """The float32 toy artifact, its weights, and the logits its programs
    gave for PROMPTS through the predictor's own dispatch."""
    art, w = _export(tmp_path_factory.mktemp('qwen') / 'art')
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, _prompts(), N_NEW)
    return art, w, tokens, logits


def _row_errors(w, tokens, logits, **over):
    """Per compared row, the largest |reference logit - served logit|."""
    errs = []
    for p, t, lg in zip(_prompts(), tokens, logits):
        seq = np.zeros(TOY['max_cache_len'], np.int64)   # one traced shape;
        n = len(p) + len(t) - 1                          # causal: the pad
        seq[:n] = np.concatenate([p, t[:-1]])            # cannot reach back
        want = np.asarray(ref.logits(w, seq, **dict(REF, **over)))
        want = want[len(p) - 1:n]
        assert want.shape == lg.shape
        errs.append(np.abs(want - lg).max(axis=-1))
    return errs


# float32 weights, pools and states differ from the reference by summation
# order alone — and by the chunked rule's other association of the same
# products (measured 3.6e-7 on logits of standard deviation 0.16): 3e-6
F32_TOL = 3e-6


@pytest.mark.parametrize('k', range(len(PROMPTS)),
                         ids=['%d_tokens' % n for n in PROMPTS])
def test_slices_then_decode_through_the_state_match_reference_logits(
        served, k):
    """Prefill in 1, 2, 3 and 5 slices (a short last one among them) hands
    state and tail from slice to slice; 32 decode steps carry them on."""
    _, w, tokens, logits = served
    errs = _row_errors(w, tokens, logits)[k]
    assert len(errs) == N_NEW and errs.max() <= F32_TOL


@pytest.mark.parametrize('control, least', [
    ({'reset_every': 16}, 1e-2),            # measured 0.19 at the median
    ({'state_dtype': jnp.bfloat16}, 4e-4)])  # measured 1.5e-3
def test_a_state_lost_or_rounded_fails_the_comparison(served, control,
                                                      least):
    """THE CONTROLS: a reference whose recurrent state is zeroed at every
    slice boundary (what a program that did not carry it would compute),
    and one that rounds the state to bfloat16 after every token, both lie
    far outside the tolerance the served programs meet."""
    _, w, tokens, logits = served
    errs = np.concatenate(_row_errors(w, tokens, logits, **control)[1:])
    assert np.median(errs) > least > 100 * F32_TOL, np.median(errs)


def _ctx(**attrs):
    return types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))


def _rule_inputs(rng, rows, c, hk=2, hv=4, dk=8, dv=8):
    n = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return {'Q': [n(rows, c, hk * dk)], 'K': [n(rows, c, hk * dk)],
            'V': [n(rows, c, hv * dv)], 'A': [n(rows, c, hv)],
            'B': [n(rows, c, hv)],
            'ALog': [jnp.asarray(rng.uniform(-5, -1.5, hv)
                                 .astype(np.float32))],
            'DtBias': [n(hv) * 0.1]}


@pytest.mark.parametrize('c, take, sub', [(16, 16, 64), (16, 11, 8),
                                          (128, 77, 64), (8, 0, 64)])
def test_chunked_rule_is_the_recurrence_from_a_carried_state(c, take, sub):
    """gated_delta_chunk against gated_delta_step alone, token by token,
    from a NON-ZERO state with chunk_len < C (and 0): the outputs of the
    real positions, and the state left at chunk_len, not at C."""
    rng = np.random.RandomState(c + take)
    slots, hv, dk, dv = 3, 4, 8, 8
    ins = _rule_inputs(rng, 1, c)
    state = jnp.asarray(rng.randn(slots, hv, dk, dv).astype(np.float32))
    chunk = dict(ins, State=[state],
                 Start=[jnp.full((1, 1), 5, jnp.int32)],
                 ChunkLen=[jnp.full((1, 1), take, jnp.int32)],
                 StateSlot=[jnp.full((1, 1), 1, jnp.int32)])
    got = lao._gated_delta_chunk(
        _ctx(n_key_head=2, n_value_head=4, sub_chunk=sub), chunk)
    # the recurrence: slot 1 live (its table starts with a real block)
    table = jnp.asarray([[0, 0], [7, 0], [0, 0]], jnp.int32)
    want_state, outs = state, []
    for t in range(take):
        step = {k: [jnp.broadcast_to(v[0][:, t], (slots,) + v[0].shape[2:])]
                for k, v in ins.items() if k not in ('ALog', 'DtBias')}
        step.update(ALog=ins['ALog'], DtBias=ins['DtBias'],
                    State=[want_state], BlockTable=[table])
        out = lao._gated_delta_step(_ctx(n_key_head=2, n_value_head=4),
                                    step)
        want_state = out['StateOut'][0]
        outs.append(np.asarray(out['Out'][0][1]))
    np.testing.assert_allclose(np.asarray(got['StateOut'][0]),
                               np.asarray(want_state), rtol=2e-5, atol=2e-5)
    # the other slots' states are nobody's business
    np.testing.assert_array_equal(np.asarray(got['StateOut'][0])[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    if take:
        np.testing.assert_allclose(np.asarray(got['Out'][0])[0, :take],
                                   np.stack(outs), rtol=2e-5, atol=2e-5)
        assert np.abs(np.stack(outs)).max() > 0.05


def test_the_chunks_solve_is_lapack_free_off_the_tpu():
    """The chunked rule's unit lower triangular solve: on the cpu the rows
    are solved one by one (lax's triangular solve is a LAPACK call there,
    and a decode artifact's serialized cpu executable that held one crashed
    the process that loaded it: a warm benchmark rehearsal), with
    triangular_solve's own numbers."""
    rng = np.random.default_rng(5)
    n = 16
    unit = np.tril(rng.normal(size=(2, 3, n, n)), -1).astype(np.float32) \
        + np.eye(n, dtype=np.float32)
    rhs = rng.normal(size=(2, 3, n, 5)).astype(np.float32)
    got = lao._solve_by_rows(jnp.asarray(unit), jnp.asarray(rhs))
    np.testing.assert_allclose(got, np.linalg.solve(unit, rhs), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(
        lao._solve_p.bind(jnp.asarray(unit), jnp.asarray(rhs)), got)
    text = jax.jit(lao._solve_p.bind).lower(unit, rhs).as_text()
    assert 'lapack' not in text and 'triangular' not in text


def test_a_chunk_that_starts_its_prompt_is_born_zero_and_nobody_writes():
    """Start == 0: the slot's previous tenant leaves nothing behind. A slot
    outside [0, max_slots) is nobody's: a zero state read, none written."""
    rng = np.random.RandomState(7)
    ins = _rule_inputs(rng, 1, 8)
    state = jnp.asarray(rng.randn(3, 4, 8, 8).astype(np.float32))
    ctx = _ctx(n_key_head=2, n_value_head=4)

    def run(state, start, slot):
        return lao._gated_delta_chunk(ctx, dict(
            ins, State=[state], Start=[jnp.full((1, 1), start, jnp.int32)],
            ChunkLen=[jnp.full((1, 1), 8, jnp.int32)],
            StateSlot=[jnp.full((1, 1), slot, jnp.int32)]))
    dirty, clean = run(state, 0, 2), run(jnp.zeros_like(state), 0, 2)
    np.testing.assert_array_equal(np.asarray(dirty['Out'][0]),
                                  np.asarray(clean['Out'][0]))
    np.testing.assert_array_equal(np.asarray(dirty['StateOut'][0])[2],
                                  np.asarray(clean['StateOut'][0])[2])
    nobody = run(state, 0, 3)
    np.testing.assert_array_equal(np.asarray(nobody['StateOut'][0]),
                                  np.asarray(state))
    np.testing.assert_array_equal(np.asarray(nobody['Out'][0]),
                                  np.asarray(clean['Out'][0]))


def test_the_convolutions_tail_crosses_a_slice_boundary():
    """Two slices of 8 with the tail carried (the first short: 5 of 8), then
    two steps, equal ONE causal convolution over the 15 inputs; an idle
    row's tail is left alone."""
    rng = np.random.RandomState(5)
    width, ch, slots = 4, 12, 3
    x = rng.randn(15, ch).astype(np.float32)
    w = jnp.asarray(rng.randn(width, ch).astype(np.float32))
    padded = np.concatenate([np.zeros((width - 1, ch), np.float32), x])
    pre = sum(np.asarray(w)[j] * padded[j:j + 15] for j in range(width))
    want = pre / (1 + np.exp(-pre))
    tail = jnp.asarray(rng.randn(slots, width - 1, ch).astype(np.float32))
    start = tail

    def chunk(tail, rows, at, take):
        buf = np.zeros((1, 8, ch), np.float32)
        buf[0, :take] = rows
        return lao._causal_conv_chunk(_ctx(), {
            'X': [jnp.asarray(buf)], 'Weight': [w], 'Tail': [tail],
            'Start': [jnp.full((1, 1), at, jnp.int32)],
            'ChunkLen': [jnp.full((1, 1), take, jnp.int32)],
            'StateSlot': [jnp.full((1, 1), 1, jnp.int32)]})
    a = chunk(tail, x[:5], 0, 5)
    b = chunk(a['TailOut'][0], x[5:13], 5, 8)
    got = [np.asarray(a['Out'][0])[0, :5], np.asarray(b['Out'][0])[0]]
    tail = b['TailOut'][0]
    table = jnp.asarray([[0], [3], [0]], jnp.int32)
    for t in (13, 14):
        out = lao._causal_conv_step(_ctx(), {
            'X': [jnp.broadcast_to(jnp.asarray(x[t]), (slots, ch))],
            'Weight': [w], 'Tail': [tail], 'BlockTable': [table]})
        tail = out['TailOut'][0]
        got.append(np.asarray(out['Out'][0])[1:2])
    np.testing.assert_allclose(np.concatenate(got), want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail)[[0, 2]],
                                  np.asarray(start)[[0, 2]])
    np.testing.assert_allclose(np.asarray(tail)[1], x[12:], rtol=0, atol=0)


def test_partial_rotary_and_the_gated_norm_are_the_references():
    rng = np.random.RandomState(2)
    x = rng.randn(9, 4 * 16).astype(np.float32)
    pos = rng.randint(0, 500, 9)
    attrs = dict(n_head=4, theta=1e7, rotary_dim=4)
    got = np.asarray(llm_ops._rotary_embedding(
        _ctx(**attrs), {'X': [jnp.asarray(x)],
                        'Pos': [jnp.asarray(pos)]})['Out'][0])
    want = np.asarray(ref.rope_partial(jnp.asarray(x), jnp.asarray(pos), 4,
                                       1e7, 4))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # the channels past rotary_dim pass, the first ones turn
    heads = got.reshape(9, 4, 16)
    np.testing.assert_array_equal(heads[..., 4:],
                                  x.reshape(9, 4, 16)[..., 4:])
    assert np.abs(heads[..., :4] - x.reshape(9, 4, 16)[..., :4]).max() > 0.1
    o, z = rng.randn(5, 4, 8).astype(np.float32), rng.randn(5, 4, 8)
    scale = (1 + 0.1 * rng.randn(8)).astype(np.float32)
    got = np.asarray(lao._gated_rms_norm(_ctx(epsilon=1e-6), {
        'X': [jnp.asarray(o)], 'Gate': [jnp.asarray(z, jnp.float32)],
        'Scale': [jnp.asarray(scale)]})['Y'][0])
    want = (o / np.sqrt((o ** 2).mean(-1, keepdims=True) + 1e-6) * scale
            * (z / (1 + np.exp(-z))))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_decay_is_seeded_to_remember():
    """A_log's range puts a head's per-token decay at a + dt_bias = 0
    between 0.9 and 0.999: a state carried wrongly shows."""
    lo, hi = decay_log_range((0.9, 0.999))
    decay = lambda a_log: np.exp(-np.exp(a_log) * np.log(2.0))
    np.testing.assert_allclose([decay(hi), decay(lo)], [0.9, 0.999],
                               rtol=1e-6)


def test_the_embedding_is_seeded_apart_and_rounding_compounds_less():
    """`embed_std` seeds the table alone (models/qwen3_next.py, THE
    EMBEDDING'S SCALE): every other matrix keeps init_std, and with the
    embedding a few times a mixer's update the reference one precision
    down stands nearer the float32 one than with the mixers as the whole
    stream — at the same logits' scale, and with the context still in
    them."""
    def build(embed_std):
        scope = fluid.core.Scope()
        with fluid.scope_guard(scope), fluid.unique_name.guard():
            spec = build_decode_spec(weights_dtype='float32',
                                     kv_cache_dtype='float32',
                                     embed_std=embed_std, **TOY)
            spec['startup'].random_seed = 3
            fluid.Executor(fluid.CPUPlace()).run(spec['startup'],
                                                 scope=scope)
            return {n: np.asarray(scope.get(n))
                    for n in scope.local_var_names()
                    if n not in spec['cache_vars']}
    seq = np.concatenate(_prompts((70,)))
    other = np.concatenate(_prompts((70,), seed=4))
    other[-1] = seq[-1]
    read = {}
    for std in (None, 0.05):    # a toy mixer's update is ~0.02, not 0.6
        w = build(std)
        assert np.std(w['embed_w']) == pytest.approx(std or 0.02, rel=0.05)
        assert np.std(w['lm_head_w']) == pytest.approx(0.02, rel=0.05)
        assert np.std(w['l0_lin_out_w']) == pytest.approx(0.02, rel=0.05)
        want = np.asarray(ref.logits(w, seq, **REF))
        low = np.asarray(ref.logits(w, seq, compute_dtype=jnp.bfloat16,
                                    **REF))
        moved = np.asarray(ref.logits(w, other, **REF))[-1]
        read[std] = (np.median(np.abs(want - low).max(axis=-1))
                     / want.std(),
                     np.abs(want[-1] - moved).max() / want.std())
    assert read[0.05][0] < 0.6 * read[None][0]     # rounding shows less
    assert read[0.05][1] > 0.5                     # the context still does


# -- who may touch a slot's state when -------------------------------------
def _state_rows(pred, slot):
    """{state var: the slot's row} of the recurrent states, host arrays."""
    names = pred._sig['block']['recurrent']['cache_vars']
    at = {e['name']: i for i, e in enumerate(pred._sig['state'])}
    return {n: np.asarray(pred._state[at[n]])[slot] for n in names}


def _solo(art, prompt, max_new):
    """A request served alone in a fresh predictor: (tokens, the state rows
    its slot is left with)."""
    with DecodingPredictor(art) as pred:
        toks = list(pred.generate(prompt, max_new_tokens=max_new,
                                  timeout=120))
        assert pred.drain(60)
        return toks, _state_rows(pred, 0)


def _assert_rows_equal(got, want):
    for n in want:
        np.testing.assert_allclose(got[n], want[n], rtol=1e-6, atol=1e-6)
        assert np.abs(want[n]).max() > 1e-3


def test_seven_slots_step_between_the_slices_of_an_eighth(served):
    """A prompt prefilled in three slices (16 + 16 + 8) while seven other
    slots decode: between its slices it is an IDLE ROW of every step, and
    the step leaves its state alone — its tokens and the state it is left
    with are those of the same request served alone."""
    art = served[0]
    late = _prompts([40], seed=11)[0]
    want, want_rows = _solo(art, late, 12)
    others = _prompts([3, 4, 5, 6, 7, 8, 9], seed=12)
    with DecodingPredictor(art) as pred:
        watch = watch_feed(pred)
        streams = [pred.submit(p, max_new_tokens=60) for p in others]
        firsts = [next(iter(s)) for s in streams]      # all seven decode
        stream = pred.submit(late, max_new_tokens=12)
        got = list(stream.result(120))
        for s in streams:       # they step on, the eighth's row idle
            s.result(120)
        assert pred.drain(60)
        rows = _state_rows(pred, 7)
        snap = pred.stats.snapshot()
    assert len(firsts) == 7 and got == want
    _assert_rows_equal(rows, want_rows)
    assert watch.steps > 0
    # the 40-token prompt's three slices, one of them its first
    assert snap['state_resets'] == 8
    assert snap['state_rows_kept'] >= 2        # idle between its slices


def test_slices_that_wait_for_a_ticks_budget_carry_their_slots_state(served):
    """Three prompts of three, three and two slices admitted in ONE tick
    beside a decoding row: a tick holds one largest chunk (16 tokens by
    bucket), so slices wait — a slot's state is touched only by the slice
    that is dispatched, and idles through the steps in between. Tokens
    and the state each slot is left with are those of the request served
    alone."""
    art = served[0]
    late = _prompts([40, 33, 21], seed=52)
    want = [_solo(art, p, 8) for p in late]
    with DecodingPredictor(art) as pred:
        feed, ticks = watch_feed(pred), watch_slices(pred)
        first = pred.submit(_prompts([5], seed=53)[0], max_new_tokens=60)
        assert next(iter(first)) is not None        # it decodes
        gate = gated(pred)
        streams = [pred.submit(p, max_new_tokens=8) for p in late]
        gate.set()
        got = [list(s.result(120)) for s in streams]
        first.result(120)
        assert pred.drain(60)
        rows = [_state_rows(pred, slot) for slot in (1, 2, 3)]
        snap = pred.stats.snapshot()
        budget = pred._rows * pred._chunks[-1]
    assert budget == 16 and feed.steps > 8
    assert got == [tokens for tokens, _ in want]
    for have, (_, want_rows) in zip(rows, want):
        _assert_rows_equal(have, want_rows)
    bound = [t for t in ticks if t['decoding']]
    assert all(t['went'] == fits_first(t['due'], budget) for t in bound)
    waited = sum(len(t['due']) - len(t['went']) for t in ticks)
    assert snap['slices_deferred'] == waited >= 8
    # a state is born once a prompt, in the slice that starts it
    assert snap['state_resets'] == 4 and snap['chunk_slices'] == 9
    assert snap['state_rows_kept'] >= waited


def test_a_slots_second_tenant_finds_nothing_of_the_first(served):
    """The state is born zero inside the chunk program where start == 0:
    the second request of a slot is served as in a fresh predictor, tokens
    and state alike, with no dispatch in between."""
    art = served[0]
    first, second = _prompts([30, 19], seed=21)
    want, want_rows = _solo(art, second, 10)
    with DecodingPredictor(art) as pred:
        pred.generate(first, max_new_tokens=20, timeout=120)
        assert np.abs(_state_rows(pred, 0)['rec_state_0']).max() > 1e-3
        calls = pred.stats.snapshot()
        got = list(pred.generate(second, max_new_tokens=10, timeout=120))
        assert pred.drain(60)
        rows = _state_rows(pred, 0)
        snap = pred.stats.snapshot()
    assert got == want
    _assert_rows_equal(rows, want_rows)
    # two slices (16 + 3 of 8) and nine steps: nothing else was dispatched
    assert snap['chunk_dispatches'] - calls['chunk_dispatches'] == 2
    assert snap['state_resets'] - calls['state_resets'] == 1


def test_a_step_dispatched_ahead_of_an_eos_changes_nothing_later(served):
    """An eos is seen a tick late: the row rides ONE more step, which moves
    the finished request's state. The slot's next tenant reads none of
    it."""
    art = served[0]
    first, second = _prompts([12, 27], seed=31)
    want, want_rows = _solo(art, second, 9)
    with DecodingPredictor(art) as pred:
        plain = list(pred.generate(first, max_new_tokens=12, timeout=120))
        pred._eos = plain[5]            # the host's: the sixth token ends it
        pred.stats.reset()
        cut = list(pred.generate(first, max_new_tokens=12, timeout=120))
        pred._eos = 1
        got = list(pred.generate(second, max_new_tokens=9, timeout=120))
        assert pred.drain(60)
        wasted = pred.stats.snapshot()['wasted_rows']
        rows = _state_rows(pred, 0)
    assert cut == plain[:plain.index(plain[5]) + 1]
    assert wasted >= 1
    assert got == want
    _assert_rows_equal(rows, want_rows)


def test_prefix_beams_and_verify_are_refused_by_name(served, tmp_path):
    art = served[0]
    with DecodingPredictor(art) as pred:
        tokens = np.arange(2, 40)
        with pytest.raises(ValueError, match='recurrent layers'):
            pred.block_manager.match_prefix(tokens)
        with pytest.raises(ValueError, match='recurrent layers'):
            pred.block_manager.register_prefix(tokens, [1, 2, 3, 4])
        with pytest.raises(ValueError, match='beam search is refused on an '
                           'artifact with recurrent layers'):
            pred.submit(tokens, max_new_tokens=4, beam=2).result(60)
        # the same prompt twice: nothing is shared, nothing is published
        a = pred.generate(tokens, max_new_tokens=5, timeout=120)
        b = pred.generate(tokens, max_new_tokens=5, timeout=120)
        snap = pred.stats.snapshot()
        assert list(a) == list(b) and snap['prefix_hits'] == 0
    with pytest.raises(ValueError, match='verify program'):
        DecodingPredictor(art, draft='ngram')
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(weights_dtype='float32',
                                 kv_cache_dtype='float32', **TOY)
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        spec['verify'] = dict(spec['step'])
        spec['draft_k'] = 2
        with pytest.raises(ValueError, match='recurrent layers has no '
                           'speculative verify program'):
            export_decode(spec, str(tmp_path / 'art'), scope=scope,
                          precompile=False)
        del spec['verify']
        spec['chunk_rows'] = dict(spec['chunk'][16], size=16, rows=4)
        with pytest.raises(ValueError, match='no row program'):
            export_decode(spec, str(tmp_path / 'art'), scope=scope,
                          precompile=False)


def test_the_artifact_says_what_the_states_are(served):
    """The state list, the feeds, the zeros program and the block copy's
    flags: a recurrent layer's states are [max_slots, ...], fed by no
    table, born zero, copied by no block pair."""
    art = served[0]
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    S, nb = TOY['max_slots'], TOY['max_slots'] * 16 + 1
    want = []
    for i, t in enumerate(layer_types(4)):
        want += ([('kv_k_%d' % i, [nb, 8, 32]), ('kv_v_%d' % i, [nb, 8, 32])]
                 if t == FULL else
                 [('rec_state_%d' % i, [S, 4, 8, 8]),
                  ('rec_conv_%d' % i, [S, 3, 64])])
    assert [(e['name'], e['shape']) for e in sig['state']] \
        == want + [('decode_ids_row', [S])]
    assert all(e['dtype'] == 'float32' for e in sig['state'][:-1])
    assert sig['block']['recurrent'] == {'cache_vars': [
        n for i in LINEAR_LAYERS for n in ('rec_state_%d' % i,
                                           'rec_conv_%d' % i)]}
    assert 'chunk_rows' not in sig and 'verify' not in sig
    assert [e['name'] for e in sig['step']['feeds']] == [
        'tokens', 'pos', 'block_tables']
    assert [e['name'] for e in sig['chunk']['16']['feeds']] == [
        'chunk_ids', 'start', 'chunk_len', 'block_table', 'state_slot',
        'slot']
    state_bytes = S * 3 * (4 * 8 * 8 + 3 * 64) * 4
    assert decoding.pool_facts(sig) == (
        2 * 32 * 4, {'kv': 2 * nb * 8 * 32 * 4, 'recurrent': state_bytes})
    with DecodingPredictor(art) as pred:
        assert all(not np.asarray(s).any() for s in pred._state)
        assert pred.stats.snapshot()['recurrent_state_bytes'] == state_bytes
        pred.generate(np.arange(2, 30), max_new_tokens=4, timeout=120)
        assert pred.drain(60)
        before = [np.asarray(s) for s in pred._state]
        pred._dispatch_blockcopy([(5, 1), (6, 2)])
        after = [np.asarray(s) for s in pred._state]
        for e, b, a in zip(sig['state'], before, after):
            if e['name'].startswith('kv_'):
                assert b[1].any() and not np.array_equal(b[5], b[1])
                np.testing.assert_array_equal(a[[5, 6]], b[[1, 2]])
            else:       # the per-slot states and the ids row: untouched
                np.testing.assert_array_equal(a, b)
        # warmup: every program once with nobody's slot and idle rows
        pred.warmup()
        assert all(not np.asarray(s).any() for s in pred._state)


def test_linear_attention_lowers_under_its_scopes():
    """The scopes the benchmark's linear_attention_* metrics read, in the
    step and in a chunk program, and the rule's op types."""
    import re
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(**TOY)
    for program, rule in ((spec['step']['program'], 'gated_delta_step'),
                          (spec['chunk'][16]['program'],
                           'gated_delta_chunk')):
        ops = program.global_block().ops
        scopes = {}
        for op in ops:
            scopes.setdefault(op.attr('op_namescope') or '', set()).add(
                op.type)
        by_part = {part: set().union(*(t for s, t in scopes.items()
                                       if 'linear_attention/%s' % part in s))
                   for part in ('in_proj', 'conv', 'delta_rule',
                                'gated_norm', 'out_proj')}
        assert rule in by_part['delta_rule']
        assert re.fullmatch(r'gated_delta\w*', rule)
        assert any(t.startswith('causal_conv') for t in by_part['conv'])
        assert 'gated_rms_norm' in by_part['gated_norm']
        assert 'mul' in by_part['in_proj'] and 'mul' in by_part['out_proj']
        gate = set().union(*(t for s, t in scopes.items()
                             if 'full_attention/gate' in s))
        assert {'sigmoid', 'elementwise_mul'} <= gate
        assert sum(op.type == rule for op in ops) == len(LINEAR_LAYERS)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST, on the reference's routed layer as this
    configuration calls it: 16 experts over 8 chips, 2 a chip. The routed
    parts the 8 offsets give, plus the gated shared expert counted once,
    are the uncut layer (every expert held) — and one share alone is not."""
    rng = np.random.RandomState(11)
    d, f, e = 64, 32, 16
    n = lambda *s: (rng.randn(*s) * 0.2).astype(np.float32)
    whole = {'l1_moe_router': n(d, e) * 2, 'l1_moe_gate': n(e, d, f),
             'l1_moe_up': n(e, d, f), 'l1_moe_down': n(e, f, d),
             'l1_shared_gate_proj_w': n(d, 1), 'l1_shared_gate_w': n(d, f),
             'l1_shared_up_w': n(d, f), 'l1_shared_down_w': n(f, d)}
    x = jnp.asarray(rng.randn(40, d).astype(np.float32))

    def share(c):
        held = dict(whole, **{'l1_moe_' + k: whole['l1_moe_' + k][
            2 * c:2 * c + 2] for k in ('gate', 'up', 'down')})
        return np.asarray(ref.feed_forward(x, held, 1, 4, 2 * c, True,
                                           shared=False))
    with jax.default_matmul_precision('highest'):
        want = np.asarray(ref.feed_forward(x, whole, 1, 4, 0, True))
        shared = want - np.asarray(ref.feed_forward(x, whole, 1, 4, 0, True,
                                                    shared=False))
        parts = [share(c) for c in range(8)]
    assert np.abs(shared).max() > 1e-2
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-6)
    assert np.abs(parts[0] + shared - want).max() > 1e-2


@pytest.mark.parametrize('shape', [(4, 4, 16, 128), (3, 2, 8, 256)])
def test_step_kernel_is_the_jnp_body(shape):
    """ops/pallas_delta_rule.py in interpret mode against the expression
    XLA fuses: live rows' outputs and states, an idle row's state left as
    it is."""
    from paddle_tpu.ops import pallas_delta_rule as pdr
    rng = np.random.RandomState(sum(shape))
    slots, heads, dk, dv = shape
    n = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    q, k, v = n(slots, heads, dk), n(slots, heads, dk), n(slots, heads, dv)
    g, beta = -jnp.abs(n(slots, heads)) * 0.1, jax.nn.sigmoid(n(slots, heads))
    state = n(slots, heads, dk, dv)
    live = jnp.asarray([True, False, True, True][:slots])
    assert pdr.refuses(state) is None
    o, new = pdr.delta_step(q, k, v, g, beta, state, live, interpret=True)
    want_o, want = pdr.jnp_step(q, k, v, g, beta, state, live)
    at = np.asarray(live)
    np.testing.assert_allclose(np.asarray(o)[at], np.asarray(want_o)[at],
                               rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new), np.asarray(want), rtol=1e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new)[1], np.asarray(state)[1])


def test_the_step_kernel_says_by_name_what_it_does_not_take():
    from paddle_tpu.ops import pallas_delta_rule as pdr
    sds = jax.ShapeDtypeStruct
    assert pdr.refuses(sds((128, 32, 128, 128), jnp.float32)) is None
    assert 'not float32' in pdr.refuses(sds((8, 4, 8, 128), jnp.bfloat16))
    assert 'tiles' in pdr.refuses(sds((8, 4, 8, 8), jnp.float32))
    assert 'tiles' in pdr.refuses(sds((8, 4, 12, 128), jnp.float32))
    assert 'lanes' in pdr.refuses(sds((8, 72, 8, 128), jnp.float32))
    assert 'bytes' in pdr.refuses(sds((8, 64, 128, 256), jnp.float32))


def test_a_state_the_kernel_takes_is_exported_with_both_bodies(tmp_path):
    """A state of whole tiles (value heads of 128): the step's rule lowers
    to the primitive that carries the kernel for a TPU and the jnp
    expression for everything else — the signature says so, and on the cpu
    the artifact serves that expression, to the reference's logits."""
    art, w = _export(tmp_path / 'art', lin_dv=128, max_slots=4)
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    assert sig['step']['attention']['gated_delta_step'] == {'kernel': 3}
    assert 'gated_delta_step' not in sig['chunk']['16']['attention']
    prompts = _prompts((21,))
    with DecodingPredictor(art) as pred:
        assert pred.attention_bodies['step']['gated_delta_step'] \
            == {'jnp': 3}
        tokens, logits = served_logits(pred, prompts, 6)
    seq = np.concatenate([prompts[0], np.asarray(tokens[0][:-1], np.int64)])
    want = np.asarray(ref.logits(w, seq, **dict(REF, dv=128)))[20:]
    assert np.abs(want - logits[0]).max() <= F32_TOL
    # the toy's 8-wide heads are no whole tile: the jnp expression alone
    assert pdr_refuses_toy()


def pdr_refuses_toy():
    from paddle_tpu.ops import pallas_delta_rule as pdr
    return 'tiles' in pdr.refuses(jax.ShapeDtypeStruct((8, 4, 8, 8),
                                                       jnp.float32))


def _chunk_kernel_inputs(rng, rows, c, takes, hk=1, hv=2, dk=128, dv=128,
                         slots=3):
    """What the op hands the chunk kernel: q, k normalised per KEY head,
    g, beta and k zero from ChunkLen on."""
    n = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    q = lao.l2_normalize(n(rows, c, hk, dk)) * dk ** -0.5
    k = lao.l2_normalize(n(rows, c, hk, dk))
    g = -jnp.abs(n(rows, c, hv)) * 0.1
    beta = jax.nn.sigmoid(n(rows, c, hv))
    clen = jnp.asarray(takes, jnp.int32)
    real = (jnp.arange(c)[None, :] < clen[:, None])[..., None]
    return (q.reshape(rows, c, -1),
            jnp.where(real[..., None], k, 0.0).reshape(rows, c, -1),
            n(rows, c, hv * dv), jnp.where(real, g, 0.0),
            jnp.where(real, beta, 0.0), n(slots, hv, dk, dv), clen)


# (C, ChunkLen a row, Start a row, slot a row): the carried-state cases of
# test_chunked_rule_is_the_recurrence_from_a_carried_state at lengths the
# kernel takes (inside a sub-chunk, nothing at all), a chunk that ends AT a
# sub-chunk's edge and a whole one, Start == 0 over a dirty slot, a slot
# outside [0, S), two rows with different slots
_CHUNK_KERNEL_CASES = [
    (256, (177,), (5,), (1,)), (128, (0,), (5,), (1,)),
    (256, (128,), (5,), (1,)), (128, (128,), (5,), (1,)),
    (384, (260,), (0,), (2,)), (128, (100,), (0,), (7,)),
    (128, (100,), (3,), (-1,)), (256, (256, 40), (0, 9), (2, 0))]


@pytest.mark.parametrize('c, takes, starts, at', _CHUNK_KERNEL_CASES)
def test_chunk_kernel_is_the_jnp_body_and_the_recurrence(c, takes, starts,
                                                         at):
    """ops/pallas_delta_chunk.py in interpret mode against delta_chunk (the
    expression XLA lowers) AND against delta_step token by token: the real
    rows' outputs, ZERO rows from ChunkLen on, the state left at ChunkLen in
    the row's slot and every other slot's as it was."""
    from paddle_tpu.ops import pallas_delta_chunk as pdc
    rng = np.random.RandomState(c + sum(takes))
    rows, hv, dk = len(takes), 2, 128
    q, k, v, g, beta, state, clen = _chunk_kernel_inputs(rng, rows, c, takes)
    assert pdc.refuses(state, q, c) is None
    args = (q, k, v, g, beta, state, jnp.asarray(starts, jnp.int32), clen,
            jnp.asarray(at, jnp.int32))
    o, new = pdc.delta_chunk(*args, interpret=True)
    want_o, want = pdc.jnp_chunk(*args, sub=64)
    touched = [s for s in at if 0 <= s < state.shape[0]]
    np.testing.assert_allclose(np.asarray(new), np.asarray(want), rtol=1e-5,
                               atol=2e-5)
    others = [s for s in range(state.shape[0]) if s not in touched]
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(state)[others])
    for r, take in enumerate(takes):
        np.testing.assert_allclose(np.asarray(o)[r, :take],
                                   np.asarray(want_o)[r, :take], rtol=1e-5,
                                   atol=2e-5)
        assert not np.asarray(o)[r, take:].any()
        # the recurrence, from the state the row starts from
        s = (state[min(max(at[r], 0), state.shape[0] - 1)][None]
             if starts[r] else jnp.zeros_like(state[:1]))
        head = lambda x, d: lao.per_value_head(
            x[r].reshape(c, 1, -1, d), hv)
        qs, ks = head(q, dk), head(k, dk)
        vs = v[r].reshape(c, 1, hv, -1)
        outs = []
        for t in range(take):
            out, s = lao.delta_step(qs[t], ks[t], vs[t], g[r, t][None],
                                    beta[r, t][None], s)
            outs.append(np.asarray(out[0]).reshape(-1))
        if take:
            np.testing.assert_allclose(np.asarray(o)[r, :take],
                                       np.stack(outs), rtol=2e-5, atol=2e-5)
            assert np.abs(np.stack(outs)).max() > 0.05
        if 0 <= at[r] < state.shape[0]:
            np.testing.assert_allclose(np.asarray(new)[at[r]],
                                       np.asarray(s[0]), rtol=2e-5,
                                       atol=2e-5)


@pytest.mark.parametrize('hard', ['beta_one', 'no_decay', 'parallel_keys',
                                  'all_three'])
def test_the_kernels_inverse_is_the_row_by_row_solve(hard):
    """(I + L)^-1 as the kernel forms it — substitution inside 16-row
    blocks, merged by products — against _solve_by_rows where the solve is
    hardest: beta at 1, no decay, keys nearly parallel (L's entries near
    1: a truncated series, or powers of L, would lose every digit)."""
    from paddle_tpu.ops import pallas_delta_chunk as pdc
    from jax.experimental import pallas as pl
    rng = np.random.RandomState(len(hard))
    n, d = 128, 128
    k = rng.randn(n, d).astype(np.float32)
    if hard in ('parallel_keys', 'all_three'):
        k = k[:1] + 0.05 * k
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    beta = (np.ones(n) if hard in ('beta_one', 'all_three')
            else rng.uniform(0.2, 0.9, n)).astype(np.float32)
    g = (np.zeros(n) if hard in ('no_decay', 'all_three')
         else -rng.uniform(0.0, 0.2, n)).astype(np.float32)
    G = np.cumsum(g)
    full = (beta[:, None] * k) @ k.T * np.exp(G[:, None] - G[None, :])
    lower = jnp.asarray(np.tril(full, -1).astype(np.float32))
    rhs = jnp.asarray(rng.randn(n, d).astype(np.float32))

    def kernel(lower_ref, out_ref):
        out_ref[...] = pdc.unit_lower_inverses([lower_ref[...]])[0]
    inverse = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=True)(lower)
    got = jnp.matmul(inverse, rhs, precision=jax.lax.Precision.HIGHEST)
    want = lao._solve_by_rows(lower + jnp.eye(n), rhs)
    exact = np.linalg.solve(np.asarray(lower, np.float64) + np.eye(n),
                            np.asarray(rhs, np.float64))
    scale = np.abs(exact).max()
    assert np.abs(np.asarray(got) - exact).max() <= max(
        4 * np.abs(np.asarray(want) - exact).max(), 2e-6 * scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-5 * scale)


def test_the_chunk_kernel_says_by_name_what_it_does_not_take():
    from paddle_tpu.ops import pallas_delta_chunk as pdc
    sds = jax.ShapeDtypeStruct
    state = sds((128, 32, 128, 128), jnp.float32)
    q = sds((1, 512, 16 * 128), jnp.float32)
    assert pdc.refuses(state, q, 512) is None
    assert pdc.refuses(state, q, 128) is None
    assert 'not float32' in pdc.refuses(
        sds((8, 32, 128, 128), jnp.bfloat16), q, 512)
    assert 'lane' in pdc.refuses(sds((8, 4, 8, 128), jnp.float32),
                                 sds((1, 128, 16), jnp.float32), 128)
    assert 'lane' in pdc.refuses(sds((8, 4, 128, 64), jnp.float32),
                                 sds((1, 128, 256), jnp.float32), 128)
    assert 'value heads' in pdc.refuses(sds((8, 3, 128, 128), jnp.float32),
                                        sds((1, 128, 256), jnp.float32), 128)
    assert 'sub-chunks' in pdc.refuses(state, q, 64)
    assert 'fast memory' in pdc.refuses(state, q, 8192)
    # the toy's 8-wide heads and 16-token chunks: the jnp expression alone
    assert pdc.refuses(sds((8, 4, 8, 8), jnp.float32),
                       sds((1, 16, 16), jnp.float32), 16)


def test_a_chunk_the_kernel_takes_is_exported_with_both_bodies(tmp_path):
    """Heads of 128 and slices of whole 128-token sub-chunks: each chunk program's
    rule lowers to the primitive that carries the kernel for a TPU and
    delta_chunk for everything else — the signature says so, on the cpu
    attention_bodies reads jnp, and the artifact serves the reference's
    logits through slices that carry the state."""
    art, w = _export(tmp_path / 'art', lin_dk=128, lin_dv=128, max_slots=4,
                     chunk_sizes=(128, 256), max_cache_len=512)
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    for size in ('128', '256'):
        assert sig['chunk'][size]['attention']['gated_delta_chunk'] \
            == {'kernel': 3}
    assert sig['step']['attention']['gated_delta_step'] == {'kernel': 3}
    prompts = _prompts((300,))         # a whole slice of 256, then 44 of 128
    with DecodingPredictor(art) as pred:
        for prog in ('chunk_128', 'chunk_256'):
            assert pred.attention_bodies[prog]['gated_delta_chunk'] \
                == {'jnp': 3}
        tokens, logits = served_logits(pred, prompts, 4)
    seq = np.concatenate([prompts[0], np.asarray(tokens[0][:-1], np.int64)])
    want = np.asarray(ref.logits(w, seq, **dict(REF, dk=128, dv=128)))[299:]
    assert np.abs(want - logits[0]).max() <= F32_TOL


def test_bfloat16_is_what_the_stated_precision_costs(tmp_path):
    """The stated precision (bfloat16 weights and K/V, float32 state) moves
    the served logits by far more than float32 rounding and far less than
    the reference one precision down — state in bfloat16 included."""
    art, w = _export(tmp_path / 'art', dtype='bfloat16')
    prompts = _prompts((21, 40))
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, prompts, 8)
    served_err, low_err = [], []
    for p, t, lg in zip(prompts, tokens, logits):
        seq = np.concatenate([p, np.asarray(t[:-1], np.int64)])
        want = np.asarray(ref.logits(w, seq, **REF))[len(p) - 1:]
        low = np.asarray(ref.logits(w, seq, compute_dtype=jnp.bfloat16,
                                    **REF))[len(p) - 1:]
        served_err.append(np.abs(want - lg).max(-1))
        low_err.append(np.abs(want - low).max(-1))
    served_err = np.median(np.concatenate(served_err))
    low_err = np.median(np.concatenate(low_err))
    assert 100 * F32_TOL < served_err < low_err
