"""Granite 4.0-H decode serving on the cpu at a toy size that keeps the shape
(two periods of M M M M M A M M M M = 20 layers; hidden 256, 4 query / 2 K/V
heads of 64, 8 Mamba-2 heads of 64 in one group, d_state 8, vocab 128, tied
head, the four multipliers as published), seeded weights: prefill in slices
through the SSD chunk's matrix form (sub-chunks of 8: a chunk of 16 is two)
with state and convolution tail carried from slice to slice, then decode
through cache and state, against the plain reference's full-forward LOGITS
(benchmark/reference/granite_hybrid.py: the recurrence position after
position from a zero state, attention over the published heads of 64); the
controls that have to fail; the chunk op against the step alone; who may
touch a slot's state; the padded heads; what is refused by name."""
import types

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, export_decode
from paddle_tpu.ops import state_space_ops as sso
from paddle_tpu.testing.decode_logits import served_logits
from benchmark.reference import granite_hybrid as ref
from models.granite_hybrid import (ATTENTION, MAMBA, build_decode_spec,
                                   layer_types)

TOY = dict(vocab=128, d_model=256, n_head=4, n_kv_head=2, d_ff=256,
           n_layer=20, ssm_heads=8, ssm_head_dim=64, d_state=8, sub_chunk=8,
           attention_multiplier=1.0 / 64, max_slots=8, max_cache_len=128,
           block_size=8, chunk_sizes=(8, 16),
           # 0.05 x sqrt(256) is what 0.02 x sqrt(2,048) is at the
           # published widths: projections of O(1)
           init_std=0.05)
# one slice; two slices, the last short (16 + 5); three (16 + 16 + 8 of 8);
# five with a short last one (4 x 16 + 6 of 8)
PROMPTS = (5, 21, 40, 70)
N_NEW = 25                  # the prompt's last slice, then 24 decode steps


def _ref_kw(**over):
    toy = dict(TOY, **over)
    return dict(layer_types=tuple(toy.get('types')
                                  or layer_types(toy['n_layer'])),
                n_head=toy['n_head'], n_kv_head=toy['n_kv_head'],
                ssm_heads=toy['ssm_heads'], d_state=toy['d_state'],
                embedding_multiplier=12.0,
                attention_multiplier=toy['attention_multiplier'],
                residual_multiplier=0.22, logits_scaling=8.0)


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
    art, w, spec = _export(tmp_path_factory.mktemp('granite') / 'art')
    with DecodingPredictor(art) as pred:
        bodies = pred.attention_bodies
        tokens, logits = served_logits(pred, _prompts(), N_NEW)
    return art, w, spec, tokens, logits, bodies


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
# order alone (measured 5.1e-7 on logits of standard deviation 0.12): 5e-6
F32_TOL = 5e-6


@pytest.mark.parametrize('k', range(len(PROMPTS)),
                         ids=['%d_tokens' % n for n in PROMPTS])
def test_slices_then_decode_match_the_reference_logits(served, k):
    """Prefill in 1, 2, 3 and 5 slices (a short last one among them) hands
    state and tail on; 24 decode steps carry everything on."""
    _, w, _, tokens, logits, _ = served
    errs = _row_errors(w, _prompts(), tokens, logits, **_ref_kw())[k]
    assert len(errs) == N_NEW and errs.max() <= F32_TOL


@pytest.mark.parametrize('control, least', [
    ({'state_dtype': jnp.bfloat16}, 5e-5),
    ({'compute_dtype': jnp.bfloat16}, 1e-3)])
def test_a_precision_below_the_stated_one_fails_the_comparison(
        served, control, least):
    """THE CONTROLS: a reference that rounds the recurrence's state to
    bfloat16 after every position, and one that computes in bfloat16
    throughout, both lie far outside the tolerance the served programs
    meet."""
    _, w, _, tokens, logits, _ = served
    errs = np.concatenate(_row_errors(w, _prompts(), tokens, logits,
                                      **dict(_ref_kw(), **control))[1:])
    assert np.median(errs) > least >= 10 * F32_TOL, np.median(errs)


def test_a_softmax_at_the_usual_scale_is_another_model(served):
    """attention_multiplier is 1/64, not 64^-1/2: a reference at the usual
    scale is far away, so the scale is part of what the agreement shows —
    and so is each of the other three multipliers."""
    _, w, _, tokens, logits, _ = served
    for over in ({'attention_multiplier': 64 ** -0.5},
                 {'residual_multiplier': 1.0},
                 {'embedding_multiplier': 1.0}, {'logits_scaling': 1.0}):
        errs = np.concatenate(_row_errors(
            w, _prompts(), tokens, logits, **dict(_ref_kw(), **over)))
        assert np.median(errs) > 100 * F32_TOL, (over, np.median(errs))


def test_the_signature_says_which_bodies_serve(served):
    """Every program names its recurrence's body a Mamba layer and its
    attention's a full layer, beside the chunk's page writes."""
    spec, bodies = served[2], served[5]
    kinds = layer_types(TOY['n_layer'])
    assert kinds == ([MAMBA] * 5 + [ATTENTION] + [MAMBA] * 4) * 2
    n_mamba, n_attn = kinds.count(MAMBA), kinds.count(ATTENTION)
    assert bodies['step'] == {'ssd_step': {'jnp': n_mamba},
                              'kv_block_attention': {'jnp': n_attn}}
    for size in TOY['chunk_sizes']:
        assert bodies['chunk_%d' % size] == {
            'ssd_chunk': {'jnp': n_mamba},
            'kv_block_chunk_write': {'pages': 2 * n_attn},
            'kv_block_chunk_attention': {'blocked': n_attn}}
    assert spec['recurrent']['cache_vars'] == [
        'rec_%s_%d' % (name, i) for i, t in enumerate(kinds) if t == MAMBA
        for name in ('ssm', 'conv')]
    assert 'chunk_rows' not in spec and 'window' not in spec
    # the state as published, d_state last; the tail over x | B | C
    block = spec['step']['program'].global_block()
    assert tuple(block.var('rec_ssm_0').shape) == (8, 8, 64, 8)
    assert tuple(block.var('rec_conv_0').shape) == (8, 3, 8 * 64 + 2 * 8)


def test_heads_of_64_ride_in_tiles_of_128(served):
    """One attention op a full layer and program, at H query heads of 128
    over KV / 2 grouped heads at the published scale: a K/V row is read
    once, and no rotary op is anywhere."""
    spec = served[2]
    for prog in [spec['step']] + list(spec['chunk'].values()):
        ops = prog['program'].global_block().ops
        attend = [op for op in ops if 'attention' in op.type]
        assert len(attend) == 2
        assert {(op.attr('n_head'), op.attr('n_kv_head')) for op in attend} \
            == {(TOY['n_head'], TOY['n_kv_head'] // 2)}
        assert all(abs(op.attr('scale') - 1.0 / 64) < 1e-12 for op in attend)
        assert not [op.type for op in ops if 'rope' in op.type
                    or 'rotary' in op.type]
        # pad and unpad are products with one constant, not slices and a
        # concat: that form of the unpad is MISCOMPILED by the TPU compiler
        # (models/granite_hybrid.py; PERF.md 6, PR 55)
        assert not [op.type for op in ops if op.type == 'concat']
        assert len([op for op in ops if op.type == 'assign_value']) == 2


@pytest.mark.parametrize('heads', [(8, 4), (16, 8)],
                         ids=['two_tiles', 'four_tiles'])
def test_several_tiles_of_padded_heads_match_the_reference(tmp_path, heads):
    """The published shape has FOUR tiles of two K/V heads (32 / 8); the
    toy above has one. An attention layer under a Mamba layer at 8 / 4 and
    16 / 8 heads of 64, against the reference's published heads."""
    n_head, n_kv = heads
    over = dict(d_model=64 * n_head, n_head=n_head, n_kv_head=n_kv, d_ff=128,
                n_layer=2, types=[ATTENTION, MAMBA], ssm_heads=2 * n_head,
                init_std=0.9 / (64 * n_head) ** 0.5, max_slots=2)
    art, w, _ = _export(tmp_path / 'art', **over)
    prompts = _prompts((40, 70))
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, prompts, 9)
    errs = np.concatenate(_row_errors(w, prompts, tokens, logits,
                                      **_ref_kw(**over)))
    assert errs.max() <= F32_TOL


def test_streams_served_together_are_the_streams_served_alone(served):
    art = served[0]
    prompts = _prompts((21, 40, 5))
    with DecodingPredictor(art) as pred:
        alone = [pred.generate(p, max_new_tokens=10, timeout=120)
                 for p in prompts]
        streams = [pred.submit(p, max_new_tokens=10) for p in prompts]
        together = [list(s.result(120)) for s in streams]
        snap = pred.stats.snapshot()
    assert [list(a) for a in alone] == together
    # 18 Mamba layers x 8 slots x (the state + the tail), float32
    assert snap['recurrent_state_bytes'] == 18 * 8 * 4 * (
        8 * 64 * 8 + 3 * (8 * 64 + 2 * 8))


# -- the SSD ops --------------------------------------------------------------
def _ctx(**attrs):
    return types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))


H, P, N = 4, 8, 6


def _ssd_inputs(rng, rows, c):
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return {'X': [f(rows, c, H * P)], 'Dt': [f(rows, c, H) * 0.5 - 2.0],
            'B': [f(rows, c, N)], 'C': [f(rows, c, N)],
            'ALog': [jnp.asarray(rng.uniform(0.0, 2.7, H)
                                 .astype(np.float32))],
            'DtBias': [f(H) * 0.1], 'D': [1.0 + f(H) * 0.1]}


_SHARED = ('ALog', 'DtBias', 'D')


def _chunk(ins, state, start, take, slot, sub):
    return sso._ssd_chunk(_ctx(n_head=H, sub_chunk=sub), dict(
        ins, State=[state], Start=[jnp.full((1, 1), start, jnp.int32)],
        ChunkLen=[jnp.full((1, 1), take, jnp.int32)],
        StateSlot=[jnp.full((1, 1), slot, jnp.int32)]))


@pytest.mark.parametrize('c, take, sub', [
    (16, 16, 8), (16, 11, 8), (16, 3, 8), (128, 77, 32), (20, 20, 8),
    (8, 0, 8), (12, 12, 256)])
def test_the_chunk_form_is_the_step_from_a_carried_state(c, take, sub):
    """ssd_chunk (the matrix form, sub-chunks of `sub`) against ssd_step
    alone, position by position, from a NON-ZERO state (Start != 0) with
    chunk_len no multiple of the sub-chunk, inside the first sub-chunk, 0,
    and a chunk that is no whole number of sub-chunks: the outputs of the
    real positions, and the state left at chunk_len, not at C."""
    rng = np.random.RandomState(c + take)
    slots = 3
    ins = _ssd_inputs(rng, 1, c)
    state = jnp.asarray(rng.randn(slots, H, P, N).astype(np.float32))
    got = _chunk(ins, state, 5, take, 1, sub)
    table = jnp.asarray([[0, 0], [7, 0], [0, 0]], jnp.int32)
    want_state, outs = state, []
    for t in range(take):
        step = {k: [jnp.broadcast_to(v[0][:, t], (slots,) + v[0].shape[2:])]
                for k, v in ins.items() if k not in _SHARED}
        step.update({k: ins[k] for k in _SHARED}, State=[want_state],
                    BlockTable=[table])
        out = sso._ssd_step(_ctx(n_head=H), step)
        want_state = out['StateOut'][0]
        outs.append(np.asarray(out['Out'][0][1]))
    np.testing.assert_allclose(np.asarray(got['StateOut'][0]),
                               np.asarray(want_state), rtol=2e-5, atol=2e-5)
    # the other slots' states are nobody's business, TO THE BIT: an idle
    # row of the step (the trash table) and a slot the chunk was not told
    np.testing.assert_array_equal(np.asarray(got['StateOut'][0])[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    np.testing.assert_array_equal(np.asarray(want_state)[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    if take:
        np.testing.assert_allclose(np.asarray(got['Out'][0])[0, :take],
                                   np.stack(outs), rtol=2e-5, atol=2e-5)
        assert np.abs(np.asarray(want_state)[1]
                      - np.asarray(state)[1]).max() > 0.01
    else:
        np.testing.assert_array_equal(np.asarray(got['StateOut'][0]),
                                      np.asarray(state))


def test_two_slices_hand_the_state_on():
    """A prompt of 13 tokens as slices of 8 and 5-of-8 (sub-chunks of 4)
    leaves the state one chunk over all 13 leaves."""
    rng = np.random.RandomState(2)
    ins = _ssd_inputs(rng, 1, 16)
    state = jnp.zeros((2, H, P, N), jnp.float32)

    def run(state, lo, hi, c):
        part = {k: ([jnp.pad(v[0][:, lo:hi],
                             ((0, 0), (0, c - (hi - lo)), (0, 0)))]
                    if k not in _SHARED else v) for k, v in ins.items()}
        return _chunk(part, state, lo, hi - lo, 0, 4)
    whole = run(state, 0, 13, 16)
    a = run(state, 0, 8, 8)
    b = run(a['StateOut'][0], 8, 13, 8)
    np.testing.assert_allclose(np.asarray(b['StateOut'][0]),
                               np.asarray(whole['StateOut'][0]), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(b['Out'][0])[0, :5],
                               np.asarray(whole['Out'][0])[0, 8:13],
                               rtol=1e-5, atol=1e-5)


def test_a_chunk_that_starts_its_prompt_is_born_zero_and_nobody_writes():
    """Start == 0 over a DIRTY slot: the slot's previous tenant leaves
    nothing behind. A slot outside [0, max_slots) is nobody's: a zero state
    read, none written."""
    rng = np.random.RandomState(7)
    ins = _ssd_inputs(rng, 1, 8)
    state = jnp.asarray(rng.randn(3, H, P, N).astype(np.float32))
    dirty = _chunk(ins, state, 0, 8, 2, 4)
    clean = _chunk(ins, jnp.zeros_like(state), 0, 8, 2, 4)
    np.testing.assert_array_equal(np.asarray(dirty['Out'][0]),
                                  np.asarray(clean['Out'][0]))
    np.testing.assert_array_equal(np.asarray(dirty['StateOut'][0])[2],
                                  np.asarray(clean['StateOut'][0])[2])
    nobody = _chunk(ins, state, 0, 8, 3, 4)
    np.testing.assert_array_equal(np.asarray(nobody['StateOut'][0]),
                                  np.asarray(state))
    nobody = _chunk(ins, state, 0, 8, -1, 4)
    np.testing.assert_array_equal(np.asarray(nobody['StateOut'][0]),
                                  np.asarray(state))


def test_an_idle_row_of_the_step_keeps_its_state_to_the_bit():
    rng = np.random.RandomState(11)
    ins = {k: ([v[0][:, 0]] if k not in _SHARED else v)
           for k, v in _ssd_inputs(rng, 3, 1).items()}
    state = jnp.asarray(rng.randn(3, H, P, N).astype(np.float32))
    out = sso._ssd_step(_ctx(n_head=H), dict(
        ins, State=[state],
        BlockTable=[jnp.asarray([[4, 0], [0, 9], [2, 0]], jnp.int32)]))
    new = np.asarray(out['StateOut'][0])
    np.testing.assert_array_equal(new[1], np.asarray(state)[1])
    assert np.abs(new[[0, 2]] - np.asarray(state)[[0, 2]]).max() > 0.01
    # the step is the recurrence, written out: a S + (delta x) B^T, S C + D x
    x = np.asarray(ins['X'][0]).reshape(3, H, P)
    delta = np.log1p(np.exp(np.asarray(ins['Dt'][0])
                            + np.asarray(ins['DtBias'][0])))
    a = np.exp(-delta * np.exp(np.asarray(ins['ALog'][0])))
    want = (a[..., None, None] * np.asarray(state)
            + (delta[..., None] * x)[..., None]
            * np.asarray(ins['B'][0])[:, None, None, :])
    np.testing.assert_allclose(new[0], want[0], rtol=1e-5, atol=1e-6)
    y = (want * np.asarray(ins['C'][0])[:, None, None, :]).sum(-1) \
        + np.asarray(ins['D'][0])[:, None] * x
    np.testing.assert_allclose(np.asarray(out['Out'][0])[2],
                               y[2].reshape(-1), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize('form', ['chunk', 'step'])
def test_the_mixer_is_transformers_mamba2_mixer(form):
    """The convolution and SSD ops, chained as models/granite_hybrid.py
    chains them, against `transformers` models/mamba2/modeling_mamba2.py
    Mamba2Mixer.torch_forward with the same weights (the granitemoehybrid
    mixer is that class's copy)."""
    torch = pytest.importorskip('torch')
    mm = pytest.importorskip('transformers.models.mamba2.modeling_mamba2')
    from transformers import Mamba2Config
    from paddle_tpu.ops import linear_attention_ops as lao
    d, heads, p, n, t = 16, 4, 8, 6, 12
    di, xbc_w = heads * p, heads * p + 2 * n
    cfg = Mamba2Config(hidden_size=d, state_size=n, conv_kernel=4, expand=2,
                       num_heads=heads, head_dim=p, n_groups=1,
                       use_bias=False, use_conv_bias=True, chunk_size=4,
                       num_hidden_layers=1, vocab_size=8,
                       time_step_limit=(0.0, float('inf')))
    torch.manual_seed(0)
    mixer = mm.Mamba2Mixer(cfg, layer_idx=0).eval()
    with torch.no_grad():
        mixer.conv1d.bias.normal_(0, 0.3)
        mixer.dt_bias.uniform_(-4.0, -1.0)
        mixer.D.normal_(1.0, 0.1)
        mixer.A_log.uniform_(0.0, 2.7)
        mixer.norm.weight.normal_(1.0, 0.1)
        x = torch.randn(1, t, d)
        want = mixer.torch_forward(x).numpy()[0]
    g = lambda q: jnp.asarray(q.detach().numpy())
    zxd = jnp.asarray(x.numpy()[0]) @ g(mixer.in_proj.weight).T
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + xbc_w], zxd[:, di + xbc_w:]
    conv_w = g(mixer.conv1d.weight)[:, 0, :].T               # [K, channels]
    shared = dict(ALog=[g(mixer.A_log)], DtBias=[g(mixer.dt_bias)],
                  D=[g(mixer.D)])
    split = lambda c: (c[..., :di], c[..., di:di + n], c[..., di + n:])
    if form == 'chunk':
        rows = dict(Start=[jnp.zeros((1, 1), jnp.int32)],
                    ChunkLen=[jnp.full((1, 1), t, jnp.int32)],
                    StateSlot=[jnp.zeros((1, 1), jnp.int32)])
        conv = lao._causal_conv_chunk(_ctx(), dict(
            rows, X=[xbc[None]], Weight=[conv_w],
            Bias=[g(mixer.conv1d.bias)],
            Tail=[jnp.zeros((1, 3, xbc_w))]))['Out'][0]
        xs, b, c = split(conv)
        m = sso._ssd_chunk(_ctx(n_head=heads, sub_chunk=8), dict(
            rows, X=[xs], Dt=[dt[None]], B=[b], C=[c],
            State=[jnp.zeros((1, heads, p, n))], **shared))['Out'][0][0]
    else:
        table = jnp.ones((1, 1), jnp.int32)
        tail, state, outs = (jnp.zeros((1, 3, xbc_w)),
                             jnp.zeros((1, heads, p, n)), [])
        for i in range(t):
            out = lao._causal_conv_step(_ctx(), {
                'X': [xbc[i:i + 1]], 'Weight': [conv_w], 'Tail': [tail],
                'Bias': [g(mixer.conv1d.bias)], 'BlockTable': [table]})
            conv, tail = out['Out'][0], out['TailOut'][0]
            xs, b, c = split(conv)
            out = sso._ssd_step(_ctx(n_head=heads), dict(
                shared, X=[xs], Dt=[dt[i:i + 1]], B=[b], C=[c],
                State=[state], BlockTable=[table]))
            state = out['StateOut'][0]
            outs.append(out['Out'][0][0])
        m = jnp.stack(outs)
    gated = m * (z * (1 / (1 + jnp.exp(-z))))
    normed = gated * jnp.reciprocal(jnp.sqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + cfg.layer_norm_epsilon))
    got = (normed * g(mixer.norm.weight)) @ g(mixer.out_proj.weight).T
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


# -- what is refused, by name -------------------------------------------------
def test_prefix_beams_verify_and_the_row_program_are_refused_by_name(
        served, tmp_path):
    art = served[0]
    with DecodingPredictor(art) as pred:
        tokens = np.arange(2, 40)
        with pytest.raises(ValueError, match='prefix reuse is refused on a '
                           'cache with recurrent layers'):
            pred.block_manager.match_prefix(tokens)
        with pytest.raises(ValueError, match='recurrent layers'):
            pred.block_manager.register_prefix(tokens, [1, 2, 3, 4])
        with pytest.raises(ValueError, match='beam search is refused on an '
                           'artifact with recurrent layers'):
            pred.submit(tokens, max_new_tokens=4, beam=2).result(60)
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


@pytest.mark.parametrize('over, said', [
    (dict(n_kv_head=1), 'whole pairs of 128-lane tiles'),
    (dict(n_head=3), 'n_head must divide d_model'),
    (dict(types=['mamba'] * 3), 'names a mixer'),
    (dict(types=['mamba'] * 19 + ['window']), 'names a mixer'),
    (dict(kv_cache_dtype='int8'), 'recurrent layers')])
def test_the_builder_refuses_what_it_cannot_build_by_name(over, said):
    with fluid.unique_name.guard(), pytest.raises(ValueError, match=said):
        build_decode_spec(**dict(TOY, **over))
