"""Kimi Linear decode serving on the cpu at a toy size that keeps the shape
(two periods of K K K F = 8 layers: 6 Kimi Delta Attention, 2 NoPE latent
attention; layer 0 dense, the rest a share of 4 of 16 routed experts; hidden
64, 2 KDA heads of 16, 4 MLA heads of 16 | 8 / 16 over a latent of 32),
seeded weights: the per-channel delta rule's step and chunk bodies against
the token-by-token recurrence; prefill in 1, 2 and 3 slices through the
chunked rule with state, convolution tail and latent pages carried from slice
to slice, then decode through cache and state, against the plain reference's
full-forward LOGITS (benchmark/reference/kimi_linear.py: the recurrence from
a zero state, latent attention expanded); the controls that have to fail;
the share test; who may touch a slot's state; the counter of carried
slices."""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, export_decode
from paddle_tpu.ops import linear_attention_ops as lao
from paddle_tpu.ops import pallas_delta_rule as pdr
from paddle_tpu.testing.decode_logits import served_logits
from benchmark.reference import exaone_moe
from benchmark.reference import kimi_linear as ref
from models.kimi_linear import KDA, MLA, build_decode_spec, layer_types

TOY = dict(vocab=128, d_model=64, n_layer=8, full_attn_layers=(4, 8),
           kda_heads=2, kda_head_dim=16, n_head=4, kv_lora_rank=32,
           d_nope=16, d_rope=8, d_v=16, d_dense=96, n_expert=16, n_held=4,
           expert_offset=4, d_expert=32, top_k=4, max_slots=4,
           max_cache_len=96, block_size=8, chunk_sizes=(8, 16),
           # 0.1 x sqrt(64) is what 0.02 x sqrt(2,304) is at the published
           # widths: projections of O(1)
           init_std=0.1)
# one slice; two, the last short (16 + 5 of 8); three (16 + 16 + 8 of 8)
PROMPTS = (5, 21, 40)
N_NEW = 9                   # the prompt's last slice, then 8 decode steps


def _ref_kw(**over):
    toy = dict(TOY, **over)
    return dict(n_layer=toy['n_layer'],
                full_attn_layers=toy['full_attn_layers'],
                kda_heads=toy['kda_heads'], n_head=toy['n_head'],
                d_nope=toy['d_nope'], d_rope=toy['d_rope'], d_v=toy['d_v'],
                first_dense=1, top_k=toy['top_k'],
                expert_offset=toy['expert_offset'])


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
    art, w, spec = _export(tmp_path_factory.mktemp('kimi') / 'art')
    with DecodingPredictor(art) as pred:
        bodies = pred.attention_bodies
        tokens, logits = served_logits(pred, _prompts(), N_NEW)
    return types.SimpleNamespace(art=art, w=w, spec=spec, tokens=tokens,
                                 logits=logits, bodies=bodies)


def _row_errors(w, prompts, tokens, logits, **kw):
    """Per compared row, the largest |reference logit - served logit|."""
    errs = []
    for p, t, lg in zip(prompts, tokens, logits):
        seq = np.zeros(TOY['max_cache_len'], np.int64)   # one traced shape;
        n = len(p) + len(t) - 1                          # causal: the pad
        seq[:n] = np.concatenate([p, t[:-1]])            # cannot reach back
        want = np.asarray(ref.logits(w, seq, **kw))[len(p) - 1:n]
        assert want.shape == lg.shape
        errs.append(np.abs(want - lg).max(axis=-1))
    return errs


# float32 weights, pool and state differ from the reference by summation
# order alone (measured 9.6e-6 on logits of standard deviation 0.47: the
# chunked form sums a sub-chunk at a time and the absorbed attention folds
# W_uk into the query): 5e-5
F32_TOL = 5e-5


@pytest.mark.parametrize('k', range(len(PROMPTS)),
                         ids=['%d_tokens' % n for n in PROMPTS])
def test_slices_then_decode_match_the_reference_logits(served, k):
    """Prefill in 1, 2 and 3 slices (a short last one among them) hands
    state, tail and latent pages on; 8 decode steps carry everything on."""
    errs = _row_errors(served.w, _prompts(), served.tokens, served.logits,
                       **_ref_kw())[k]
    assert len(errs) == N_NEW and errs.max() <= F32_TOL, errs.max()


@pytest.mark.parametrize('control', [
    {'scalar_decay': True}, {'reset_every': 16},
    {'state_dtype': jnp.bfloat16}], ids=lambda c: next(iter(c)))
def test_the_controls_fail_the_comparison(served, control):
    """THE CONTROLS: ONE decay a head in place of the per-channel vector (a
    Gated DeltaNet's rule), a state lost between two slices of 16, a state
    rounded to bfloat16 after every token — each lies far outside the
    tolerance the served programs meet (on the prompts of two and three
    slices: a prompt of one slice loses nothing between slices)."""
    errs = np.concatenate(_row_errors(
        served.w, _prompts(), served.tokens, served.logits,
        **dict(_ref_kw(), **control))[1:])
    assert np.median(errs) > 1000 * F32_TOL, np.median(errs)


@pytest.mark.parametrize('part', ['kda', 'decay', 'mla'])
def test_operands_rounded_in_one_part_cost_a_share_of_one_precision_down(
        served, part):
    """The reference's round_operands control (chip_smoke.py phase L reads
    the served error's origin with it): one part's matrix products taking
    their left operand through bfloat16 move the logits by more than
    float32 rounding and by less than the whole reference one precision
    down."""
    def median(**control):
        return np.median(np.concatenate(_row_errors(
            served.w, _prompts(), served.tokens, served.logits,
            **dict(_ref_kw(), **control))))
    low = median(compute_dtype=jnp.bfloat16)
    assert F32_TOL < median(round_operands=(part,)) < low


def test_bfloat16_is_what_the_stated_precision_costs(tmp_path):
    """The stated precision (bfloat16 weights and latent pool, float32
    state and tail) against the float32 reference over the same bfloat16
    weights. At toy widths ONE re-routed expert moves a row's logits by
    tenths, so no absolute bound on a row means anything here (the chip's
    bound, at published widths, is chip_smoke.py's KIMI_LOGIT_TOL): the
    served median row lies well over float32 rounding and UNDER the same
    reference one precision down — bfloat16 throughout, state included."""
    art, w, _ = _export(tmp_path / 'art', dtype='bfloat16')
    prompts = _prompts()
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, prompts, N_NEW)
    served_err = np.median(np.concatenate(
        _row_errors(w, prompts, tokens, logits, **_ref_kw())))
    low_err = np.median(np.concatenate(_row_errors(
        w, prompts, tokens, logits, compute_dtype=jnp.bfloat16,
        state_dtype=jnp.bfloat16, **_ref_kw())))
    assert 100 * F32_TOL < served_err < low_err, (served_err, low_err)


def test_the_signature_says_which_bodies_serve(served):
    """Every program names its rule's body a KDA layer and its latent
    attention's a MLA layer, beside the chunk's page writes: ONE artifact
    with a recurrent state and a latent pool."""
    kinds = layer_types(TOY['n_layer'], TOY['full_attn_layers'])
    assert kinds == [KDA, KDA, KDA, MLA] * 2
    assert served.bodies['step'] == {
        'gated_delta_step': {'jnp': 6},
        'kv_block_attention': {'latent_jnp': 2}}
    for size in TOY['chunk_sizes']:
        assert served.bodies['chunk_%d' % size] == {
            'gated_delta_chunk': {'jnp': 6},
            'kv_block_chunk_write': {'pages': 2},
            'kv_block_chunk_attention': {'blocked': 2}}
    block = served.spec['step']['program'].global_block()
    assert tuple(block.var('rec_state_0').shape) == (4, 2, 16, 16)
    assert tuple(block.var('rec_conv_0').shape) == (4, 3, 3 * 2 * 16)
    assert tuple(block.var('kv_c_3').shape) == (4 * 12 + 1, 8, 128)
    # no rotation anywhere, and one full-rank query a MLA layer
    ops = [op.type for op in block.ops]
    assert not [t for t in ops if 'rotary' in t or 'rope' in t]
    names = {v for v in block.vars}
    assert 'l3_q_w' in names and 'l3_q_a_w' not in names


def test_streams_served_together_are_the_streams_served_alone(served):
    prompts = _prompts((21, 40, 5))
    with DecodingPredictor(served.art) as pred:
        alone = [pred.generate(p, max_new_tokens=10, timeout=120)
                 for p in prompts]
    with DecodingPredictor(served.art) as pred:     # its counters from zero
        streams = [pred.submit(p, max_new_tokens=10) for p in prompts]
        together = [list(s.result(120)) for s in streams]
        snap = pred.stats.snapshot()
        log = pred.stats.tick_log()
        pred.stats.reset()
        assert pred.stats.snapshot()['slices_carried'] == 0
    assert [list(a) for a in alone] == together
    # 21 = 16 + 5, 40 = 16 + 16 + 8, 5: six slices, three of them carried
    assert (snap['chunk_slices'], snap['slices_carried'],
            snap['state_resets']) == (6, 3, 3)
    assert log['slices'].sum() == 6 and log['slices_carried'].sum() == 3
    assert np.all(log['slices_carried'] <= log['slices'])
    # six KDA layers x four slots x (the state + the tail), float32
    assert snap['recurrent_state_bytes'] == 6 * 4 * 4 * (
        2 * 16 * 16 + 3 * 3 * 2 * 16)
    assert set(snap['pool_bytes']) == {'latent', 'recurrent'}


# -- the share -----------------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer_and_head(tmp_path):
    """Guide section 4: at a toy size the eight shares' routed parts — 2 of
    16 experts each — with the shared expert, the mixer and the dense layer
    counted once, add up to the uncut reference's layer output; the
    vocabulary slices' logits concatenate to the uncut head's."""
    _, w, _ = _export(tmp_path / 'art', n_held=None, expert_offset=0)
    kw = _ref_kw(expert_offset=0)
    mix = {k: kw[k] for k in ('full_attn_layers', 'kda_heads', 'n_head',
                              'd_nope', 'd_rope', 'd_v')}
    ids = _prompts((40,))[0]
    with jax.default_matmul_precision('highest'):
        x = jnp.asarray(w['embed_w'])[ids].astype(jnp.float32)
        for i in (0, 1, 3):     # dense + KDA, routed + KDA, routed + MLA
            h = ref.mixer(x, w, i, **mix)
            hn = exaone_moe.rms_norm(
                h, jnp.asarray(w['l%d_post_attn_norm_w' % i]), 1e-5)
            route = (1, TOY['top_k'])
            whole = exaone_moe.feed_forward(hn, w, i, *route, 0, 2.446, True)
            if i == 0:      # the dense layer: every chip computes it alike
                parts = whole
            else:
                parts = exaone_moe.feed_forward(    # the shared expert, once
                    hn, dict(w, **{'l%d_moe_%s' % (i, n):
                                   w['l%d_moe_%s' % (i, n)][:0]
                                   for n in ('gate', 'up', 'down')}),
                    i, *route, 0, 2.446, True)
                for s in range(8):
                    share = dict(w, **{
                        'l%d_moe_%s' % (i, n):
                            w['l%d_moe_%s' % (i, n)][2 * s:2 * s + 2]
                        for n in ('gate', 'up', 'down')})
                    parts = parts + exaone_moe.feed_forward(
                        hn, share, i, *route, 2 * s, 2.446, True,
                        shared=False)
            np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                                       rtol=1e-5, atol=1e-6)
            x = h + whole
        head = exaone_moe._head(x, jnp.asarray(w['final_norm_w']),
                                jnp.asarray(w['lm_head_w']), eps=1e-5)
        slices = [exaone_moe._head(
            x, jnp.asarray(w['final_norm_w']),
            jnp.asarray(w['lm_head_w'][:, 16 * s:16 * s + 16]), eps=1e-5)
            for s in range(8)]
    np.testing.assert_allclose(np.concatenate(slices, axis=1),
                               np.asarray(head), rtol=1e-6, atol=1e-6)


# -- the per-channel delta rule ------------------------------------------------
H, DK, DV = 3, 16, 8


def _ctx(**attrs):
    return types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))


def _rule_inputs(rng, rows, c, g_low=-0.6, g_high=-1e-4):
    """Inputs of the op pair for `rows` rows of `c` tokens whose decays lie
    in [g_low, g_high] a token and channel: A_log 0 and DtBias the inverse
    softplus of -g, so g = -softplus(A + DtBias) with A = 0."""
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    g = rng.uniform(g_low, g_high, H * DK).astype(np.float32)
    g[:DK] = g_low                      # a whole head at the strongest
    return {'Q': [f(rows, c, H * DK)], 'K': [f(rows, c, H * DK)],
            'V': [f(rows, c, H * DV)], 'A': [jnp.zeros((rows, c, H * DK))],
            'B': [f(rows, c, H)], 'ALog': [jnp.zeros(H)],
            'DtBias': [jnp.asarray(np.log(np.expm1(-g.astype(np.float64)))
                                   .astype(np.float32))]}


_SHARED = ('ALog', 'DtBias')


def _chunk(ins, state, start, take, slot, sub=64):
    return lao._gated_delta_chunk(
        _ctx(n_key_head=H, n_value_head=H, sub_chunk=sub), dict(
            ins, State=[state], Start=[jnp.full((1, 1), start, jnp.int32)],
            ChunkLen=[jnp.full((1, 1), take, jnp.int32)],
            StateSlot=[jnp.full((1, 1), slot, jnp.int32)]))


def _recurrence(ins, state, take):
    """The reference's token-by-token rule (benchmark/reference/
    kimi_linear.py _rule_step) over the first `take` tokens of row 0 from
    `state` [H, DK, DV]: (outputs [take, H * DV], the state after)."""
    q, k, v = lao._heads(ins['Q'][0][0], ins['K'][0][0], ins['V'][0][0], H, H)
    g, beta = lao.decay_and_strength(ins['A'][0][0], ins['B'][0][0],
                                     ins['ALog'][0], ins['DtBias'][0])
    outs = []
    with jax.default_matmul_precision('highest'):
        for t in range(take):
            state, o = ref._rule_step(state, q[t], k[t], v[t], g[t], beta[t],
                                      jnp.float32)
            outs.append(np.asarray(o).reshape(-1))
    return np.stack(outs) if outs else np.zeros((0, H * DV)), state


@pytest.mark.parametrize('c, take, sub, start, g_low', [
    (16, 16, 8, 5, -0.6), (16, 11, 8, 5, -0.6), (16, 3, 8, 0, -0.6),
    (128, 77, 64, 9, -0.6), (128, 128, 64, 0, -1e-3), (8, 0, 8, 4, -0.6),
    (128, 128, 64, 64, -1.24), (128, 128, 64, 64, -2.5),
    (128, 77, 64, 0, -6.0)], ids=[
        'whole', 'short_last_slice', 'inside_the_first_sub_chunk',
        'strongest_seeded_decay', 'weakest_decay_from_zero', 'no_token',
        'at_the_bodys_limit', 'past_the_limit', 'far_past_the_limit'])
def test_the_chunked_rule_is_the_recurrence(c, take, sub, start, g_low):
    """gated_delta_chunk under a PER-CHANNEL decay against the token-by-token
    recurrence: from a carried state (Start != 0) and from zero (Start ==
    0, over a DIRTY slot), chunk_len no multiple of the sub-chunk, inside the
    first sub-chunk and 0; at the strongest decay the configuration seeds
    (e^g = 0.55 a token: 38 summed over a sub-chunk of 64) and at the body's
    own limit (1.24 x 64 = 79.4 of CHANNEL_DECAY_LIMIT 80: e^{-G} reaches
    e^79 and nothing overflows) and PAST it (2.5 and 6 a token: 160 and 384
    summed, where the op takes the recurrence itself) — the outputs of the
    real positions, and the state left at chunk_len, not at C."""
    assert 64 * 1.24 < lao.CHANNEL_DECAY_LIMIT < 88 < 64 * 2.5
    rng = np.random.RandomState(c + take)
    slots = 3
    ins = _rule_inputs(rng, 1, c, g_low=g_low)
    state = jnp.asarray(rng.randn(slots, H, DK, DV).astype(np.float32))
    got = _chunk(ins, state, start, take, 1, sub)
    want_o, want_s = _recurrence(
        ins, state[1] if start else jnp.zeros_like(state[1]), take)
    # float32 rounding; at the limit the exponents themselves (|G| ~ 79,
    # an ulp of 8e-6) cost 1e-4 relative
    tol = 2e-3 if g_low == -1.24 else 2e-5
    new = np.asarray(got['StateOut'][0])
    assert np.isfinite(new).all() and np.isfinite(got['Out'][0]).all()
    np.testing.assert_allclose(new[1], np.asarray(want_s), rtol=tol,
                               atol=tol)
    # the other slots' states are nobody's business, TO THE BIT
    np.testing.assert_array_equal(new[[0, 2]], np.asarray(state)[[0, 2]])
    np.testing.assert_allclose(np.asarray(got['Out'][0])[0, :take], want_o,
                               rtol=tol, atol=tol)
    if not take and start:
        np.testing.assert_array_equal(new, np.asarray(state))


@pytest.mark.parametrize('g_low', [-2.5, -6.0])
def test_past_the_limit_the_chunked_form_alone_is_not_a_number(g_low,
                                                               monkeypatch):
    """What the op's look at min G is for: with the limit out of the way
    the chunked form's e^{-G} overflows and the state it leaves is NaN —
    the answer a decay past the limit must never be given."""
    ins = _rule_inputs(np.random.RandomState(7), 1, 128, g_low=g_low)
    state = jnp.zeros((2, H, DK, DV), jnp.float32)
    assert np.isfinite(np.asarray(
        _chunk(ins, state, 0, 128, 1)['StateOut'][0])).all()
    monkeypatch.setattr(lao, 'CHANNEL_DECAY_LIMIT', np.inf)
    assert not np.isfinite(np.asarray(
        _chunk(ins, state, 0, 128, 1)['StateOut'][0])).all()


def test_two_slices_hand_the_state_on():
    """A prompt of 13 tokens as slices of 8 and 5-of-8 leaves the state one
    chunk over all 13 leaves."""
    rng = np.random.RandomState(2)
    ins = _rule_inputs(rng, 1, 16)
    state = jnp.zeros((2, H, DK, DV), jnp.float32)

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
    rng = np.random.RandomState(7)
    ins = _rule_inputs(rng, 1, 8)
    state = jnp.asarray(rng.randn(3, H, DK, DV).astype(np.float32))
    dirty = _chunk(ins, state, 0, 8, 2, 4)
    clean = _chunk(ins, jnp.zeros_like(state), 0, 8, 2, 4)
    np.testing.assert_array_equal(np.asarray(dirty['Out'][0]),
                                  np.asarray(clean['Out'][0]))
    np.testing.assert_array_equal(np.asarray(dirty['StateOut'][0])[2],
                                  np.asarray(clean['StateOut'][0])[2])
    for nobody in (3, -1):
        out = _chunk(ins, state, 0, 8, nobody, 4)
        np.testing.assert_array_equal(np.asarray(out['StateOut'][0]),
                                      np.asarray(state))


@pytest.mark.parametrize('body', ['jnp', 'kernel'])
def test_the_step_is_the_recurrence_and_leaves_an_idle_row_alone(body):
    """gated_delta_step under a per-channel decay, its jnp body and its
    Pallas kernel (interpret mode: heads of 128 lanes), against the
    reference's recurrence; an idle row's state is kept to the bit."""
    h, d = 2, 128
    rng = np.random.RandomState(11)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    q = lao.l2_normalize(f(3, h, d)) * d ** -0.5
    k, v = lao.l2_normalize(f(3, h, d)), f(3, h, d)
    g = jnp.asarray(rng.uniform(-0.6, -1e-4, (3, h, d)).astype(np.float32))
    beta = jax.nn.sigmoid(f(3, h))
    state = f(3, h, d, d)
    live = jnp.asarray([True, False, True])
    assert pdr.refuses(state, per_channel=True) is None
    assert 'decays' in pdr.refuses(jnp.zeros((1, 48, 8, 128)),
                                   per_channel=True)
    step = pdr.jnp_step if body == 'jnp' else (
        lambda *a: pdr.delta_step(*a, interpret=True))
    o, new = step(q, k, v, g, beta, state, live)
    with jax.default_matmul_precision('highest'):
        want_s, want_o = jax.vmap(lambda S, *xs: ref._rule_step(
            S, *xs, state_dtype=jnp.float32))(state, q, k, v, g, beta)
    np.testing.assert_array_equal(np.asarray(new)[1], np.asarray(state)[1])
    keep = [0, 2]
    np.testing.assert_allclose(np.asarray(new)[keep],
                               np.asarray(want_s)[keep], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(o)[keep], np.asarray(want_o)[keep],
                               rtol=2e-5, atol=2e-5)
    # a scalar decay is the same step with every channel alike
    o1, new1 = step(q, k, v, jnp.broadcast_to(g[..., :1], g.shape), beta,
                    state, live)
    o2, new2 = step(q, k, v, g[..., 0], beta, state, live)
    np.testing.assert_allclose(np.asarray(o1)[keep], np.asarray(o2)[keep],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new1), np.asarray(new2),
                               rtol=2e-5, atol=2e-5)


def test_the_op_tells_the_two_decays_apart_by_the_projections_width():
    """A [.., Hv] is a head's scalar decay (the Gated DeltaNet's, whose
    chunk may take the kernel); A [.., Hv * dk] the per-channel one, whose
    chunk is the jnp body."""
    rng = np.random.RandomState(5)
    ins = _rule_inputs(rng, 1, 8)
    state = jnp.zeros((1, H, DK, DV), jnp.float32)
    said = []
    ctx = _ctx(n_key_head=H, n_value_head=H, sub_chunk=4)
    ctx.tracer = types.SimpleNamespace(lowered_bodies=said)
    rows = dict(State=[state], Start=[jnp.zeros((1, 1), jnp.int32)],
                ChunkLen=[jnp.full((1, 1), 8, jnp.int32)],
                StateSlot=[jnp.zeros((1, 1), jnp.int32)])
    per_channel = lao._gated_delta_chunk(ctx, dict(ins, **rows))
    scalar = lao._gated_delta_chunk(ctx, dict(
        ins, A=[jnp.zeros((1, 8, H))], DtBias=[ins['DtBias'][0][:H]],
        **rows))
    assert said == [('gated_delta_chunk', 'jnp')] * 2
    assert per_channel['Out'][0].shape == scalar['Out'][0].shape
    assert np.abs(np.asarray(per_channel['Out'][0])
                  - np.asarray(scalar['Out'][0])).max() > 1e-3
