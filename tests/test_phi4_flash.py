"""Phi-4-mini-flash (SambaY) decode serving on the cpu at a toy size (10
layers: a self-decoder of three (Mamba, attention) periods — window 16 on
the first two, the third full — and a cross-decoder of two (GMU, cross
attention) periods; hidden 64, 4 / 2 heads of 16, d_state 4, dt_rank 8,
vocab 128, tied head), seeded weights: prefill in slices through the scan's
chunk form with its state and convolution tail carried from slice to slice
and the cross-decoder at a slice's last position only, then decode through
cache and state, against the plain reference's full-forward LOGITS
(benchmark/reference/phi4_flash.py: the scan token by token from a zero
state, differential attention as four products over the published column
layout, the cross-decoder at every position); the controls that have to
fail; the chunk op against the step alone; who may touch a slot's state;
the one-pass differential attention and its column permutation; the shared
pool's accounting; the Mamba mixer against transformers' own; what is
refused by name."""
import types

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, export_decode
from paddle_tpu.ops import linear_attention_ops as lao
from paddle_tpu.ops import state_space_ops as sso
from paddle_tpu.testing.decode_logits import served_logits
from benchmark.reference import phi4_flash as ref
from models.decode_spec import DecodeSpecBuilder
from models.phi4_flash import (CROSS, FULL, GMU, MAMBA, WINDOW,
                               build_decode_spec, half_heads, layer_types,
                               program_heads, published_columns)

TOY = dict(vocab=128, d_model=64, n_head=4, n_kv_head=2, d_ff=128,
           n_layer=10, n_self=6, window=16, d_state=4, dt_rank=8,
           max_slots=8, max_cache_len=128, block_size=8, chunk_sizes=(8, 16),
           # 0.1 x sqrt(64) is what 0.02 x sqrt(2,560) is at the published
           # widths: projections of O(1), and a scan whose state is a
           # visible part of its output (at 0.02 it is 1e-6 of it here)
           init_std=0.1)
# one slice; two slices, the last short (16 + 5); three (16 + 16 + 8 of 8);
# five with a short last one (4 x 16 + 6 of 8): past the window of 16
PROMPTS = (5, 21, 40, 70)
N_NEW = 33                  # the prompt's last slice, then 32 decode steps


def _ref_kw(**over):
    toy = dict(TOY, **over)
    dh = toy['d_model'] // toy['n_head']
    return dict(n_head=toy['n_head'], n_kv_head=toy['n_kv_head'],
                n_layer=toy['n_layer'], n_self=toy['n_self'],
                window=toy['window'], dt_rank=toy['dt_rank'],
                q_cols=published_columns(toy['n_head'], dh),
                kv_cols=published_columns(toy['n_kv_head'], dh))


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
    return art, weights, spec


def _prompts(lens=PROMPTS, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, TOY['vocab'], n) for n in lens]


@pytest.fixture(scope='module')
def served(tmp_path_factory):
    """The float32 toy artifact, its weights, its spec, and the logits its
    programs gave for PROMPTS through the predictor's own dispatch."""
    art, w, spec = _export(tmp_path_factory.mktemp('phi4') / 'art')
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, _prompts(), N_NEW)
    return art, w, spec, tokens, logits


def _row_errors(w, prompts, tokens, logits, **kw):
    """Per compared row, the largest |reference logit - served logit|."""
    errs = []
    for p, t, lg in zip(prompts, tokens, logits):
        seq = np.zeros(TOY['max_cache_len'], np.int64)   # one traced shape;
        n = len(p) + len(t) - 1                          # causal: the pad
        seq[:n] = np.concatenate([p, t[:-1]])            # cannot reach back
        want = ref.logits(w, seq, **kw)[len(p) - 1:n]
        assert want.shape == lg.shape
        errs.append(np.abs(want - lg).max(axis=-1))
    return errs


# float32 weights, pools and states differ from the reference by summation
# order alone (measured 3.5e-6 on logits of standard deviation 0.81): 1e-5
F32_TOL = 1e-5


@pytest.mark.parametrize('k', range(len(PROMPTS)),
                         ids=['%d_tokens' % n for n in PROMPTS])
def test_slices_then_decode_match_the_reference_logits(served, k):
    """Prefill in 1, 2, 3 and 5 slices (a short last one among them) hands
    state and tail on, drops the window layers' blocks past the window and
    runs the cross-decoder at each slice's last position; 32 decode steps
    carry everything on, all layers at every step."""
    _, w, _, tokens, logits = served
    errs = _row_errors(w, _prompts(), tokens, logits, **_ref_kw())[k]
    assert len(errs) == N_NEW and errs.max() <= F32_TOL


@pytest.mark.parametrize('control, least', [
    ({'state_dtype': jnp.bfloat16}, 1e-4),       # measured 2.6e-4
    ({'compute_dtype': jnp.bfloat16}, 1e-2)])    # measured 7.5e-2
def test_a_precision_below_the_stated_one_fails_the_comparison(
        served, control, least):
    """THE CONTROLS: a reference that rounds the scan's state to bfloat16
    after every token, and one that computes in bfloat16 throughout, both
    lie far outside the tolerance the served programs meet."""
    _, w, _, tokens, logits = served
    errs = np.concatenate(_row_errors(w, _prompts(), tokens, logits,
                                      **dict(_ref_kw(), **control))[1:])
    assert np.median(errs) > least >= 10 * F32_TOL, np.median(errs)


def test_the_reference_without_the_column_map_is_another_model(served):
    """The programs keep their q, k and v columns in their op's order: a
    reference that reads them as published (no map) is far away, so the
    map is part of what the agreement above shows."""
    _, w, _, tokens, logits = served
    kw = _ref_kw()
    dh = TOY['d_model'] // TOY['n_head']
    kw['q_cols'] = np.arange(TOY['n_head'] * dh)
    kw['kv_cols'] = np.arange(TOY['n_kv_head'] * dh)
    errs = np.concatenate(_row_errors(w, _prompts(), tokens, logits, **kw))
    assert np.median(errs) > 1e-3


def test_halves_are_stripes_and_a_tile_holds_a_pair():
    assert half_heads(8) == ([0, 1, 2, 3], [4, 5, 6, 7])
    assert program_heads(8) == [0, 4, 1, 5, 2, 6, 3, 7]
    cols = published_columns(4, 2)
    # published head 2 (half 2's first) lies in program slot 1
    assert cols.tolist() == [0, 1, 4, 5, 2, 3, 6, 7]


# -- the scan ---------------------------------------------------------------
def _ctx(**attrs):
    return types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))


def _scan_inputs(rng, rows, c, di=12, n=4):
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return {'X': [f(rows, c, di)], 'Dt': [f(rows, c, di) * 0.5 - 3.0],
            'B': [f(rows, c, n)], 'C': [f(rows, c, n)],
            'ALog': [jnp.asarray(rng.uniform(0.0, 2.7, (n, di))
                                 .astype(np.float32))],
            'DtBias': [f(di) * 0.1], 'D': [1.0 + f(di) * 0.1]}


_SHARED = ('ALog', 'DtBias', 'D')


@pytest.mark.parametrize('c, take', [(16, 16), (16, 11), (128, 77), (8, 0)])
def test_the_chunk_form_is_the_step_from_a_carried_state(c, take):
    """selective_scan_chunk against selective_scan_step alone, token by
    token, from a NON-ZERO state with chunk_len < C (and 0): the outputs of
    the real positions, and the state left at chunk_len, not at C."""
    rng = np.random.RandomState(c + take)
    slots, di, n = 3, 12, 4
    ins = _scan_inputs(rng, 1, c)
    state = jnp.asarray(rng.randn(slots, n, di).astype(np.float32))
    got = sso._selective_scan_chunk(_ctx(), dict(
        ins, State=[state], Start=[jnp.full((1, 1), 5, jnp.int32)],
        ChunkLen=[jnp.full((1, 1), take, jnp.int32)],
        StateSlot=[jnp.full((1, 1), 1, jnp.int32)]))
    table = jnp.asarray([[0, 0], [7, 0], [0, 0]], jnp.int32)
    want_state, outs = state, []
    for t in range(take):
        step = {k: [jnp.broadcast_to(v[0][:, t], (slots,) + v[0].shape[2:])]
                for k, v in ins.items() if k not in _SHARED}
        step.update({k: ins[k] for k in _SHARED}, State=[want_state],
                    BlockTable=[table])
        out = sso._selective_scan_step(_ctx(), step)
        want_state = out['StateOut'][0]
        outs.append(np.asarray(out['Out'][0][1]))
    np.testing.assert_allclose(np.asarray(got['StateOut'][0]),
                               np.asarray(want_state), rtol=1e-6, atol=1e-6)
    # the other slots' states are nobody's business: an idle row of the
    # step (the trash table) and a slot the chunk was not told
    np.testing.assert_array_equal(np.asarray(got['StateOut'][0])[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    np.testing.assert_array_equal(np.asarray(want_state)[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    if take:
        np.testing.assert_allclose(np.asarray(got['Out'][0])[0, :take],
                                   np.stack(outs), rtol=1e-5, atol=1e-6)
        assert np.abs(np.asarray(want_state)[1]
                      - np.asarray(state)[1]).max() > 0.01


def test_two_slices_hand_the_state_on():
    """A prompt of 13 tokens as slices of 8 and 5-of-8 leaves the state one
    chunk over all 13 leaves."""
    rng = np.random.RandomState(2)
    ins = _scan_inputs(rng, 1, 16)
    state = jnp.zeros((2, 4, 12), jnp.float32)

    def run(state, lo, hi, c):
        part = {k: ([jnp.pad(v[0][:, lo:hi],
                             ((0, 0), (0, c - (hi - lo)), (0, 0)))]
                    if k not in _SHARED else v) for k, v in ins.items()}
        return sso._selective_scan_chunk(_ctx(), dict(
            part, State=[state], Start=[jnp.full((1, 1), lo, jnp.int32)],
            ChunkLen=[jnp.full((1, 1), hi - lo, jnp.int32)],
            StateSlot=[jnp.full((1, 1), 0, jnp.int32)]))
    whole = run(state, 0, 13, 16)
    a = run(state, 0, 8, 8)
    b = run(a['StateOut'][0], 8, 13, 8)
    np.testing.assert_allclose(np.asarray(b['StateOut'][0]),
                               np.asarray(whole['StateOut'][0]), rtol=1e-6,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(b['Out'][0])[0, :5],
                               np.asarray(whole['Out'][0])[0, 8:13],
                               rtol=1e-5, atol=1e-6)


def test_a_chunk_that_starts_its_prompt_is_born_zero_and_nobody_writes():
    """Start == 0: the slot's previous tenant leaves nothing behind. A slot
    outside [0, max_slots) is nobody's: a zero state read, none written."""
    rng = np.random.RandomState(7)
    ins = _scan_inputs(rng, 1, 8)
    state = jnp.asarray(rng.randn(3, 4, 12).astype(np.float32))

    def run(state, start, slot):
        return sso._selective_scan_chunk(_ctx(), dict(
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


def test_the_convolution_adds_its_bias_before_the_silu():
    rng = np.random.RandomState(5)
    width, ch = 4, 12
    x = rng.randn(8, ch).astype(np.float32)
    w = rng.randn(width, ch).astype(np.float32)
    bias = rng.randn(ch).astype(np.float32)
    padded = np.concatenate([np.zeros((width - 1, ch), np.float32), x])
    pre = sum(w[j] * padded[j:j + 8] for j in range(width)) + bias
    got = lao._causal_conv_chunk(_ctx(), {
        'X': [jnp.asarray(x[None])], 'Weight': [jnp.asarray(w)],
        'Bias': [jnp.asarray(bias)],
        'Tail': [jnp.zeros((2, width - 1, ch), jnp.float32)],
        'Start': [jnp.zeros((1, 1), jnp.int32)],
        'ChunkLen': [jnp.full((1, 1), 8, jnp.int32)],
        'StateSlot': [jnp.zeros((1, 1), jnp.int32)]})
    np.testing.assert_allclose(np.asarray(got['Out'][0])[0],
                               pre / (1 + np.exp(-pre)), rtol=1e-5,
                               atol=1e-6)
    step = lao._causal_conv_step(_ctx(), {
        'X': [jnp.asarray(x[-1:])], 'Weight': [jnp.asarray(w)],
        'Bias': [jnp.asarray(bias)], 'Tail': [jnp.asarray(x[None, 4:7])],
        'BlockTable': [jnp.ones((1, 2), jnp.int32)]})
    np.testing.assert_allclose(np.asarray(step['Out'][0])[0],
                               (pre / (1 + np.exp(-pre)))[-1], rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize('form', ['chunk', 'step'])
def test_the_mixer_is_transformers_mamba_mixer(form):
    """The convolution and scan ops, chained as models/phi4_flash.py chains
    them, against `transformers` models/mamba/modeling_mamba.py
    MambaMixer.slow_forward with the same weights."""
    torch = pytest.importorskip('torch')
    mm = pytest.importorskip('transformers.models.mamba.modeling_mamba')
    from transformers import MambaConfig
    d, n, rank, t = 16, 4, 3, 12
    di = 2 * d
    cfg = MambaConfig(hidden_size=d, state_size=n, conv_kernel=4, expand=2,
                      time_step_rank=rank, use_bias=False,
                      use_conv_bias=True, num_hidden_layers=1, vocab_size=8)
    torch.manual_seed(0)
    mixer = mm.MambaMixer(cfg, layer_idx=0).eval()
    with torch.no_grad():
        mixer.conv1d.bias.normal_(0, 0.3)
        mixer.dt_proj.bias.uniform_(-4.0, -1.0)
        mixer.D.normal_(1.0, 0.1)
        x = torch.randn(1, t, d)
        want = mixer.slow_forward(x).numpy()[0]
    g = lambda p: jnp.asarray(p.detach().numpy())
    u, z = jnp.split(jnp.asarray(x.numpy()[0]) @ g(mixer.in_proj.weight).T,
                     2, axis=-1)
    conv_w = g(mixer.conv1d.weight)[:, 0, :].T               # [K, channels]
    shared = dict(ALog=[g(mixer.A_log).T], DtBias=[g(mixer.dt_proj.bias)],
                  D=[g(mixer.D)])

    def project(conv):
        dbc = conv @ g(mixer.x_proj.weight).T
        return (dbc[..., :rank] @ g(mixer.dt_proj.weight).T,
                dbc[..., rank:rank + n], dbc[..., rank + n:])
    if form == 'chunk':
        rows = dict(Start=[jnp.zeros((1, 1), jnp.int32)],
                    ChunkLen=[jnp.full((1, 1), t, jnp.int32)],
                    StateSlot=[jnp.zeros((1, 1), jnp.int32)])
        conv = lao._causal_conv_chunk(_ctx(), dict(
            rows, X=[u[None]], Weight=[conv_w], Bias=[g(mixer.conv1d.bias)],
            Tail=[jnp.zeros((1, 3, di))]))['Out'][0]
        dt, b, c = project(conv)
        m = sso._selective_scan_chunk(_ctx(), dict(
            rows, X=[conv], Dt=[dt], B=[b], C=[c],
            State=[jnp.zeros((1, n, di))], **shared))['Out'][0][0]
    else:
        table = jnp.ones((1, 1), jnp.int32)
        tail, state, outs = jnp.zeros((1, 3, di)), jnp.zeros((1, n, di)), []
        for i in range(t):
            out = lao._causal_conv_step(_ctx(), {
                'X': [u[i:i + 1]], 'Weight': [conv_w], 'Tail': [tail],
                'Bias': [g(mixer.conv1d.bias)], 'BlockTable': [table]})
            conv, tail = out['Out'][0], out['TailOut'][0]
            dt, b, c = project(conv)
            out = sso._selective_scan_step(_ctx(), dict(
                shared, X=[conv], Dt=[dt], B=[b], C=[c], State=[state],
                BlockTable=[table]))
            state = out['StateOut'][0]
            outs.append(out['Out'][0][0])
        m = jnp.stack(outs)
    got = (m * (z * (1 / (1 + jnp.exp(-z))))) @ g(mixer.out_proj.weight).T
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


# -- differential attention in one pass -------------------------------------
@pytest.mark.parametrize('layers, what', [((2, 2), 'full'),
                                          ((4, 4), 'window_then_full')])
def test_one_pass_differential_attention_is_the_four_products(
        tmp_path, layers, what):
    """A self-decoder alone (Mamba, full; Mamba, window, Mamba, full): the
    programs' ONE attention over [k_1 | k_2] and [v_1 | v_2] tiles with the
    query halves padded, against the reference's four attention calls over
    the published columns, past the window of 16."""
    n_layer, n_self = layers
    art, w, spec = _export(tmp_path / 'art', n_layer=n_layer, n_self=n_self)
    assert 'shared_pools' not in spec
    prompts = _prompts((40,))
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, prompts, 9)
    errs = _row_errors(w, prompts, tokens, logits,
                       **_ref_kw(n_layer=n_layer, n_self=n_self))[0]
    assert errs.max() <= F32_TOL


def test_a_large_tied_table_is_not_read_straight_back(tmp_path):
    """The benchmark's seeds at toy widths: the table seeded 8 x an update
    (THE EMBEDDING'S SCALE, models/phi4_flash.py) under a final LayerNorm
    whose weight has zero mean: logits of standard deviation ~1, the
    reference agrees, and the model does not answer a token with itself —
    which it does under the plain N(1, 0.1) weight."""
    e = 8 * 0.8                         # 0.8: an update's rms at TOY's seeds
    std = 1.0 / (e * TOY['d_model'] ** 0.5)
    prompts = _prompts((21,))

    def repeats(tag, **seeds):
        art, w, _ = _export(tmp_path / tag, embed_std=e, **seeds)
        with DecodingPredictor(art) as pred:
            tokens, logits = served_logits(pred, prompts, 17)
        return w, tokens, logits, float(np.mean(
            np.asarray(tokens[0][1:]) == np.asarray(tokens[0][:-1])))
    w, tokens, logits, same = repeats('zero_mean', final_norm_std=std)
    assert abs(w['final_ln_w'].mean()) < 3 * std / 8 \
        and w['final_ln_w'].std() == pytest.approx(std, rel=0.3)
    assert same < 0.5 and 0.5 < logits[0].std() < 2.0
    assert _row_errors(w, prompts, tokens, logits,
                       **_ref_kw())[0].max() <= F32_TOL
    # the control: the plain N(1, 0.1) gain reads the table back
    assert repeats('plain')[3] > 0.9


def test_every_cached_row_is_read_once_a_layer(served):
    """One attention op a caching or reading layer and program, at H heads
    over KV / 2 grouped heads of twice the width: no second pass."""
    spec = served[2]
    types = layer_types(TOY['n_layer'], TOY['n_self'])
    attending = sum(t in (WINDOW, FULL, CROSS) for t in types)
    for prog in [spec['step']] + list(spec['chunk'].values()):
        ops = [op for op in prog['program'].global_block().ops
               if 'attention' in op.type]
        assert len(ops) == attending
        assert {(op.attr('n_head'), op.attr('n_kv_head')) for op in ops} \
            == {(TOY['n_head'], TOY['n_kv_head'] // 2)}
        assert all(abs(op.attr('scale') - 16 ** -0.5) < 1e-9 for op in ops)


# -- the shared pool and the cross-decoder ----------------------------------
def test_cross_layers_keep_nothing_and_the_spec_says_who_reads_whom(served):
    art, _, spec, _, _ = served
    types = layer_types(TOY['n_layer'], TOY['n_self'])
    assert types == [MAMBA, WINDOW, MAMBA, WINDOW, MAMBA, FULL, GMU, CROSS,
                     GMU, CROSS]
    assert spec['shared_pools'] == {7: 5, 9: 5}
    assert [n for n in spec['cache_vars'] if n.startswith('kv_')] == [
        'kv_k_1', 'kv_v_1', 'kv_k_3', 'kv_v_3', 'kv_k_5', 'kv_v_5']
    for prog in [spec['step']] + list(spec['chunk'].values()):
        writes = [op for op in prog['program'].global_block().ops
                  if op.type.endswith('write')]
        assert len(writes) == 2 * 3         # K and V of layers 1, 3, 5
        reads = [op.input('KCache')[0]
                 for op in prog['program'].global_block().ops
                 if 'attention' in op.type]
        assert reads == ['kv_k_1', 'kv_k_3', 'kv_k_5', 'kv_k_5', 'kv_k_5']


def test_the_pool_counts_one_full_layer_and_frees_it_once(served):
    art = served[0]
    with DecodingPredictor(art) as pred:
        snap = pred.stats.snapshot()
        assert snap['shared_pool_readers'] == 2
        maxb = -(-TOY['max_cache_len'] // TOY['block_size'])
        nb = TOY['max_slots'] * maxb + 1
        row = 2 * TOY['n_kv_head'] * 16 * 4         # K and V, float32
        assert snap['pool_bytes']['kv'] == nb * TOY['block_size'] * row
        stream = pred.submit(_prompts((21,))[0], max_new_tokens=12)
        stream.result(120)
        snap = pred.stats.snapshot()
        # 21 + 11 positions written (the last token is never fed) in
        # blocks of 8: one table's, not three readers'
        assert snap['blocks_peak'] == -(-(21 + 11) // TOY['block_size'])
        assert snap['blocks_in_use'] == 0
        assert snap['window_blocks_in_use'] == 0


def test_a_chunk_runs_the_cross_decoder_at_one_position(served):
    """In a chunk program the layers from n_self on see [1, 1, D]: their
    attention has one query, their matrices one row; the step is whole."""
    spec = served[2]
    block = spec['chunk'][16]['program'].global_block()
    queries = [block.var(op.input('Q')[0]).shape
               for op in block.ops if 'attention' in op.type]
    assert [int(s[1]) for s in queries] == [16, 16, 16, 1, 1]
    rows = {int(block.var(op.input('X')[0]).shape[1])
            for op in block.ops if op.type == 'mul'
            and any(n.startswith(('l6_', 'l7_', 'l8_', 'l9_'))
                    for n in op.input('Y'))}
    assert rows == {1}


def test_streams_served_together_are_the_streams_served_alone(served):
    art = served[0]
    prompts = _prompts((21, 40, 5))
    with DecodingPredictor(art) as pred:
        alone = [pred.generate(p, max_new_tokens=10, timeout=120)
                 for p in prompts]
        streams = [pred.submit(p, max_new_tokens=10) for p in prompts]
        together = [list(s.result(120)) for s in streams]
    assert [list(a) for a in alone] == together


# -- what is refused, by name -----------------------------------------------
def _builder(**over):
    kw = dict(vocab=32, d_model=16, kv_width=16, n_layer=4, max_slots=2,
              max_cache_len=32, block_size=8, chunk_sizes=(8,),
              num_blocks=None, eos_id=1, kv_cache_dtype='float32')
    return DecodeSpecBuilder(**dict(kw, **over))


@pytest.mark.parametrize('over, said', [
    (dict(shared_pools={3: 1}, kv_cache_dtype='int8'), 'a shared pool'),
    (dict(shared_pools={3: 1}, draft_k=2), 'a shared pool'),
    (dict(shared_pools={3: 1}, mp_shard=2), 'mp_shard=2 is not built'),
    (dict(no_cache=[3], last_only_from=3, draft_k=2), 'last_only_from'),
    (dict(shared_pools={1: 3}), 'cannot attend'),
    (dict(shared_pools={3: 1}, recurrent={1: {'s': ([2], 'float32')}}),
     'cannot attend'),
    (dict(shared_pools={3: 2, 2: 1}), 'cannot attend'),
    (dict(last_only_from=2, no_cache=[3]), 'keeps a cache or a state'),
    (dict(no_cache=[1], window_layers=[1], window=8), 'no other kind')])
def test_what_cannot_hold_beside_a_shared_pool_is_refused_by_name(over,
                                                                   said):
    with pytest.raises(ValueError, match=said):
        _builder(**over)


def test_a_reader_writes_nothing_and_export_refuses_a_verify_program(
        served, tmp_path):
    b = _builder(shared_pools={3: 1})
    with pytest.raises(ValueError, match='keeps no pool'):
        b.write(3, None, None)
    assert b.cache_names(3) == [] and b.cache_names(1) == ['kv_k_1',
                                                           'kv_v_1']
    spec = dict(served[2], verify=served[2]['step'], draft_k=1)
    with pytest.raises(ValueError, match='shared pool'):
        export_decode(spec, str(tmp_path / 'art'), scope=fluid.core.Scope())
