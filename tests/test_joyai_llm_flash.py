"""JoyAI-LLM-Flash decode serving on the cpu at a toy size (3 layers, hidden
64, 4 heads of 16 + 8 rotary, q_lora_rank 24, kv_lora_rank 32, a dense
leading layer, 16 routed experts top-4 of which 4 are held from offset 4, a
shared expert, vocab 128), seeded weights: prefill in slices then paged
LATENT decode — one pool a layer, the attention absorbed — against the
plain reference's full-forward LOGITS in the published, expanded form
(benchmark/reference/joyai_llm_flash.py); absorbed = expanded on random
inputs; the interleaved rotation against the pairwise formula; what
bfloat16 costs; the same comparison against the reference with one equation
changed, which has to fail; the eight shares against the uncut layer; one
latent pool a layer in the signature and the feeds; block copy, a released
block, a prefix hit and a poisoned trash block on a latent pool; and the
latent paged kernel against the op's jnp body, in interpret mode and
compiled for a described v5e at the benchmark's widths."""
import json
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, decoding, export_decode
from paddle_tpu.ops import decode_ops
from paddle_tpu.ops import pallas_paged_attention as ppa
from paddle_tpu.testing.decode_logits import served_logits
from benchmark.reference import joyai_llm_flash as ref
from models.joyai_llm_flash import build_decode_spec, row_width

from decode_feed_check import watch_feed

TOY = dict(vocab=128, d_model=64, n_head=4, q_lora_rank=24, kv_lora_rank=32,
           d_nope=16, d_rope=8, d_v=16, n_layer=3, d_dense=96, n_expert=16,
           n_held=4, expert_offset=4, d_expert=32, top_k=4, max_slots=4,
           max_cache_len=96, block_size=8, chunk_sizes=(8, 16))
REF = dict(n_head=4, d_nope=16, d_rope=8, d_v=16, n_layer=3, first_dense=1,
           top_k=4, expert_offset=4)
ATTN = dict(n_head=4, d_nope=16, d_rope=8, d_v=16, eps=1e-6, theta=32e6)
# one slice; slices of both sizes; several key blocks; page edges
PROMPTS = (3, 21, 40, 70)
N_NEW = 12


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


def _prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(2, TOY['vocab'], n) for n in PROMPTS]


@pytest.fixture(scope='module')
def served(tmp_path_factory):
    """The float32 toy artifact, its weights, and the logits its programs
    gave for PROMPTS through the predictor's own dispatch."""
    with pytest.MonkeyPatch.context() as mp:    # several key blocks a slot
        mp.setattr(decode_ops, '_CHUNK_KEY_BLOCK', 16)
        art, w = _export(tmp_path_factory.mktemp('joyai') / 'art')
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
    return np.concatenate(errs)


# float32 weights and pools differ from the reference by summation order
# alone — and by the absorbed form's other association of the same
# products (measured 1.8e-7 on logits of standard deviation 0.10): 2e-6
F32_TOL = 2e-6


def test_slices_then_paged_latent_decode_match_reference_logits(served):
    _, w, tokens, logits = served
    assert _row_errors(w, tokens, logits).max() <= F32_TOL


def _layer_weights(seed, std=0.3):
    rng = np.random.RandomState(seed)
    h, dn, dr, dv, r, ql, d = 4, 16, 8, 16, 32, 24, 64
    n = lambda *s: (rng.randn(*s) * std).astype(np.float32)
    return {'input_norm_w': 1 + n(d), 'q_a_w': n(d, ql),
            'q_a_norm_w': 1 + n(ql), 'q_b_w': n(ql, h * (dn + dr)),
            'kv_a_w': n(d, r + dr), 'kv_a_norm_w': 1 + n(r),
            'kv_b_w': n(r, h * (dn + dv)), 'o_w': n(h * dv, d)}


@pytest.mark.parametrize('seed', [0, 1])
def test_absorbed_attention_is_the_expanded_attention(seed):
    """W_uk folded into the query and W_uv unfolded from the result give
    the published per-head keys and values' numbers, on random weights
    and inputs of no special structure."""
    w = _layer_weights(seed)
    x = np.random.RandomState(10 + seed).randn(37, 64).astype(np.float32)
    with jax.default_matmul_precision('highest'):
        expanded = np.asarray(ref.attention(jnp.asarray(x), w, **ATTN))
        absorbed = np.asarray(ref.absorbed_attention(jnp.asarray(x), w,
                                                     **ATTN))
    assert np.abs(expanded).max() > 0.1
    # outputs of magnitude ~7: float32 rounding of the two associations
    np.testing.assert_allclose(absorbed, expanded, rtol=2e-5, atol=2e-5)


def _rotary_op(x, pos, n_head, theta, **attrs):
    from paddle_tpu.ops import llm_ops
    attrs = dict(attrs, n_head=n_head, theta=theta)
    ctx = types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))
    return np.asarray(llm_ops._rotary_embedding(
        ctx, {'X': [jnp.asarray(x)], 'Pos': [jnp.asarray(pos)]})['Out'][0])


def test_interleaved_rotary_is_the_pairwise_formula():
    """rope_interleave: channels (2j, 2j+1) of each head turn by pos *
    theta^(-2j/d), in place; the reference's rope_pairs is the same
    function; the family's file moves the pairs to the halves first
    (view(d/2, 2).transpose), which changes no q . k; and without the
    attribute the op is the rotate-half one it was."""
    rng = np.random.RandomState(4)
    n_head, d, theta = 3, 8, 32e6
    x = rng.randn(5, n_head * d).astype(np.float32)
    y = rng.randn(5, n_head * d).astype(np.float32)
    pos = np.array([0, 1, 7, 300, 4000], np.int32)
    got = _rotary_op(x, pos, n_head, theta, interleave=True)
    want = np.empty((5, n_head, d), np.float64)
    xh = x.reshape(5, n_head, d).astype(np.float64)
    for j in range(d // 2):
        ang = pos * theta ** (-2.0 * j / d)
        c, s = np.cos(ang)[:, None], np.sin(ang)[:, None]
        want[..., 2 * j] = xh[..., 2 * j] * c - xh[..., 2 * j + 1] * s
        want[..., 2 * j + 1] = xh[..., 2 * j + 1] * c + xh[..., 2 * j] * s
    np.testing.assert_allclose(got, want.reshape(5, -1), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.rope_pairs(jnp.asarray(x), jnp.asarray(pos), n_head,
                                  theta)), got, rtol=1e-6, atol=1e-6)

    def as_the_file(z):      # de-interleave, then rotate-half
        halves = z.reshape(5, n_head, d // 2, 2).transpose(0, 1, 3, 2)
        return _rotary_op(halves.reshape(5, -1), pos, n_head, theta)
    rotated_y = _rotary_op(y, pos, n_head, theta, interleave=True)
    np.testing.assert_allclose(
        (as_the_file(x) * as_the_file(y)).reshape(5, n_head, d).sum(-1),
        (got * rotated_y).reshape(5, n_head, d).sum(-1), rtol=1e-4,
        atol=1e-5)
    assert np.abs(_rotary_op(x, pos, n_head, theta) - got).max() > 0.1


def test_bfloat16_is_what_the_stated_precision_costs(tmp_path):
    """bfloat16 weights and a bfloat16 latent pool, both attention
    products on bfloat16 operands: measured 1.0e-3 at the median row and
    1.2e-3 at the ninth decile on logits of standard deviation 0.16,
    bounded at about three times that — and float32's tolerance refuses
    it: the stated precision is what ran."""
    art, w = _export(tmp_path / 'art', 'bfloat16')
    with DecodingPredictor(art) as pred:
        tokens, logits = served_logits(pred, _prompts(), N_NEW)
    errs = _row_errors(w, tokens, logits)
    assert np.median(errs) <= 3e-3, np.median(errs)
    assert np.quantile(errs, 0.9) <= 8e-3, np.quantile(errs, 0.9)
    assert np.median(errs) > 100 * F32_TOL


def _rotate_half_pairs(x, pos, n_head, theta):
    """The rotation WITHOUT rope_interleave: first half with second."""
    from benchmark.reference import exaone_moe
    return exaone_moe.rope(x, pos, n_head, theta)


@pytest.mark.parametrize('what', [
    'router_bias', 'scaling', 'renormalisation', 'shared_expert',
    'rope_interleave', 'softmax_scale', 'query_norm', 'latent_norm'])
def test_a_reference_with_one_equation_changed_fails(served, monkeypatch,
                                                     what):
    """The comparison is tight enough to tell: against the reference
    without the selection bias, the scaling, the renormalisation or the
    shared expert, with rotate-half pairs, a softmax scale over the 16
    channels without position alone, or without the RMSNorm between the
    query's (the latent's) two products, the served logits are off by
    far more than the tolerance."""
    _, w, tokens, logits = served
    over = {}
    if what == 'router_bias':
        w = {k: (np.zeros_like(v) if k.endswith('router_bias') else v)
             for k, v in w.items()}
    elif what == 'scaling':
        over = {'scaling': 1.0}
    elif what == 'renormalisation':
        over = {'norm_topk_prob': False}
    elif what == 'shared_expert':
        w = {k: v for k, v in w.items() if '_shared_' not in k}
    elif what == 'rope_interleave':
        monkeypatch.setattr(ref, 'rope_pairs', _rotate_half_pairs)
    elif what == 'softmax_scale':      # 16^-1/2 in place of (16 + 8)^-1/2
        real = ref.attention
        monkeypatch.setattr(
            ref, 'attention', lambda x, lw, **kw: real(
                x, dict(lw, q_b_w=lw['q_b_w'] * (24 / 16) ** 0.5), **kw))
    elif what in ('query_norm', 'latent_norm'):
        key = 'q_a_norm_w' if what == 'query_norm' else 'kv_a_norm_w'
        real = ref.rms_norm
        monkeypatch.setattr(
            ref, 'rms_norm', lambda x, nw, eps: x if nw.shape[0] == w[
                'l0_' + key].shape[0] and x.shape[-1] != TOY['d_model']
            else real(x, nw, eps))
    inner = what in ('rope_interleave', 'softmax_scale', 'query_norm',
                     'latent_norm')
    if inner:                   # the jitted parts were traced unpatched
        jax.clear_caches()
    try:
        errs = _row_errors(w, tokens, logits, **over)
    finally:
        monkeypatch.undo()
        if inner:
            jax.clear_caches()
    assert np.median(errs) > 100 * F32_TOL, (what, np.median(errs))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST, on the reference's routed layer as this
    configuration calls it: 16 experts over 8 chips, 2 a chip. The routed
    parts the 8 offsets give, plus the shared expert counted once, are
    the uncut layer (every expert held) — and one share alone is not."""
    rng = np.random.RandomState(11)
    d, f, e = 64, 32, 16
    n = lambda *s: (rng.randn(*s) * 0.2).astype(np.float32)
    whole = {'l1_moe_router': n(d, e) * 2, 'l1_moe_router_bias': n(e),
             'l1_moe_gate': n(e, d, f), 'l1_moe_up': n(e, d, f),
             'l1_moe_down': n(e, f, d), 'l1_shared_gate_w': n(d, f),
             'l1_shared_up_w': n(d, f), 'l1_shared_down_w': n(f, d)}
    x = jnp.asarray(rng.randn(40, d).astype(np.float32))
    routed = (1, 4)      # first_dense, top_k

    def share(c):
        held = dict(whole, **{'l1_moe_' + k: whole['l1_moe_' + k][
            2 * c:2 * c + 2] for k in ('gate', 'up', 'down')})
        return np.asarray(ref.feed_forward(x, held, 1, *routed, 2 * c, 2.5,
                                           True, shared=False))
    with jax.default_matmul_precision('highest'):
        want = np.asarray(ref.feed_forward(x, whole, 1, *routed, 0, 2.5,
                                           True))
        shared = want - np.asarray(ref.feed_forward(
            x, whole, 1, *routed, 0, 2.5, True, shared=False))
        parts = [share(c) for c in range(8)]
    assert np.abs(shared).max() > 1e-2
    np.testing.assert_allclose(sum(parts) + shared, want, rtol=2e-5,
                               atol=2e-6)
    assert np.abs(parts[0] + shared - want).max() > 1e-2


def test_signature_and_feeds_name_one_latent_pool_a_layer(served):
    art = served[0]
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    width = row_width(32, 8)
    assert width == 128         # 40 values up to a whole lane tile
    assert [(e['name'], e['shape']) for e in sig['state']] == [
        ('kv_c_%d' % i, [4 * 12 + 1, 8, width]) for i in range(3)] + [
        ('decode_ids_row', [4])]
    assert sig['block']['cache_kind'] == 'latent'
    assert 'window' not in sig['block'] and 'chunk_rows' not in sig
    assert [e['name'] for e in sig['step']['feeds']] == [
        'tokens', 'pos', 'block_tables']
    assert [e['name'] for e in sig['chunk']['16']['feeds']] == [
        'chunk_ids', 'start', 'chunk_len', 'block_table', 'slot']
    assert sig['step']['attention'] == {
        'kv_block_attention': {'latent_jnp': 3}}
    with DecodingPredictor(art) as pred:
        assert pred.attention_bodies['step'] == {
            'kv_block_attention': {'latent_jnp': 3}}
        snap = pred.stats.snapshot()
    assert snap['cache_row_bytes'] == 3 * width * 4
    assert snap['pool_bytes'] == {'latent': 3 * 49 * 8 * width * 4}


def test_a_pool_the_kernel_takes_is_exported_with_both_bodies(tmp_path):
    """A latent row of whole lane tiles on whole-sublane pages (latent
    128 + rotary 8 -> 256 wide, pages of 16): the step's module holds the
    latent kernel for a TPU and the jnp body for everything else, through
    the one primitive — whose result is n_head x v_width wide, not the
    query's width — and served here it is the jnp body, to the
    reference's logits."""
    art, w = _export(tmp_path / 'art', kv_lora_rank=128, block_size=16,
                     n_layer=2)
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    assert sig['state'][0]['shape'] == [4 * 6 + 1, 16, 256]
    assert sig['step']['attention'] == {
        'kv_block_attention': {'latent_kernel': 2}}
    prompts = _prompts()[:2]
    with DecodingPredictor(art) as pred:
        assert pred.attention_bodies['step'] == {
            'kv_block_attention': {'latent_jnp': 2}}
        assert pred.stats.snapshot()['attention'] == 'jnp'
        tokens, logits = served_logits(pred, prompts, 6)
    for p, t, lg in zip(prompts, tokens, logits):
        seq = np.zeros(TOY['max_cache_len'], np.int64)
        n = len(p) + len(t) - 1
        seq[:n] = np.concatenate([p, t[:-1]])
        want = np.asarray(ref.logits(w, seq, **dict(REF, n_layer=2)))
        assert np.abs(want[len(p) - 1:n] - lg).max() <= F32_TOL


def test_a_kv_pair_spec_keeps_its_two_pools_and_reports_them(tmp_path):
    """The builder's other kind, said in the same place: an OLMoE spec
    keeps kv_k_<i> / kv_v_<i>, carries no cache_kind, and its stats file
    the pools under 'kv'."""
    from models.olmoe import build_decode_spec as olmoe_spec
    with fluid.unique_name.guard():
        spec = olmoe_spec()
    assert spec['cache_vars'][:2] == ['kv_k_0', 'kv_v_0']
    assert 'cache_kind' not in spec
    sig = {'block': {}, 'state': [
        {'name': 'kv_k_0', 'shape': [9, 8, 64], 'dtype': 'bfloat16'},
        {'name': 'kv_v_0', 'shape': [9, 8, 64], 'dtype': 'bfloat16'},
        {'name': 'decode_ids_row', 'shape': [4], 'dtype': 'int32'}]}
    assert decoding.pool_facts(sig) == (256, {'kv': 2 * 9 * 8 * 128})
    # a latent builder takes ONE tensor of rows a layer, and says so
    from models.decode_spec import DecodeSpecBuilder
    b = DecodeSpecBuilder(128, 64, 128, 1, 4, 96, 8, (8, 16), None, 1,
                          'float32', 'float32', 1e-6, 0.02, v_width=32)
    b._io = {'write': lambda c, r, kind: c}
    with fluid.unique_name.guard(), \
            fluid.program_guard(fluid.Program(), b.startup), \
            pytest.raises(ValueError, match='1 pool'):
        b.write(0, None, None)


def test_a_released_block_a_prefix_hit_and_a_block_copy_serve_unchanged(
        served):
    """Blocks are content-agnostic: more requests than slots, so that
    requests run in latent blocks others gave back, each transcript the
    one the request gets alone, the kept feed the rebuilt feed at every
    step; the same prompt again rides its published blocks (a prefix hit)
    to the same tokens; and a beam's copy-on-write moves latent blocks
    through the blockcopy program, twice the same hypotheses."""
    art = served[0]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, TOY['vocab'], n)
               for n in (33, 5, 60, 18, 47, 26, 70, 9, 52)]
    with DecodingPredictor(art) as pred:
        alone = [list(pred.generate(p, max_new_tokens=10, timeout=120))
                 for p in prompts]
        pred.block_manager.evict_all_prefixes()
        pred.stats.reset()
        watch = watch_feed(pred)
        streams = [pred.submit(p, max_new_tokens=10) for p in prompts]
        together = [list(s.result(120)) for s in streams]
        assert watch.live > 50
        hits = pred.stats.snapshot()['prefix_hits']
        again = list(pred.generate(prompts[2], max_new_tokens=10,
                                   timeout=120))
        snap = pred.stats.snapshot()
        assert snap['prefix_hits'] > hits and snap['prefix_tokens_reused']
        beams = [pred.submit(prompts[0], beam=2, max_new_tokens=8).result(
            120) for _ in range(2)]
        assert pred.stats.snapshot()['cow_blocks'] > 0
    assert together == alone and again == alone[2]
    np.testing.assert_array_equal(beams[0][0], beams[1][0])
    np.testing.assert_array_equal(beams[0][1], beams[1][1])


def test_warmup_and_a_poisoned_trash_block(served):
    """warmup() runs every program of a latent artifact; and with the
    trash block poisoned (NaN — pad lanes included: what an idle row's
    scatter may leave there), the same tokens: no table maps block 0 into
    a request's span, and rows past `pos` never reach a sum."""
    art = served[0]
    with DecodingPredictor(art) as pred:
        pred.warmup()
        clean = [list(pred.generate(p, max_new_tokens=6, timeout=120))
                 for p in _prompts()[2:]]
        for i in range(TOY['n_layer']):
            pred._state[i] = pred._state[i].at[0].set(jnp.nan)
        poisoned = [list(pred.generate(p, max_new_tokens=6, timeout=120))
                    for p in _prompts()[2:]]
    assert poisoned == clean


def test_latent_attention_lowers_under_its_scopes():
    """fluid.name_scope reaches the lowered ops' names, which is what a
    device trace shows and latent_proj_device_share reads: the low-rank
    query, the latent down-projection, q_absorb and v_expand each under
    latent_attention/, the trip through the pages under the attention
    op's own type."""
    from benchmark.layer_metrics.latent_proj_device_share import LATENT_PROJ
    with fluid.unique_name.guard():
        spec = build_decode_spec(**TOY)
    for prog in (spec['step']['program'], spec['chunk'][16]['program']):
        by_scope = {}
        for op in prog.global_block().ops:
            by_scope.setdefault(op.attrs.get('op_namescope'), []).append(
                op.type)
        assert 'matmul' in by_scope['latent_attention/q_absorb']
        assert 'matmul' in by_scope['latent_attention/v_expand']
        assert by_scope['latent_attention/q_lora'].count('mul') == 2 * 3
        assert 'rotary_embedding' in by_scope['latent_attention/kv_down']
        attend = [t for t in by_scope['latent_attention']
                  if t.startswith('kv_block')]
        assert len(attend) == 3 and len(set(attend)) == 1
    for scope in ('q_lora', 'kv_down', 'q_absorb', 'v_expand'):
        assert LATENT_PROJ.search(
            'jit(decode_step)/latent_attention/%s/mul/dot_general' % scope)
    assert not LATENT_PROJ.search(
        'jit(decode_step)/latent_attention/kv_block_attention/while')


# -- the latent paged kernel ---------------------------------------------

# where the kernel's two bodies meet (ISSUE 44): both sides of a page's
# edge, of the 256-row compute block's (a slot of 255 is one last block
# of 16 pages, of 256 a full block and a last of one row) and of the
# second block's, and the last row of a 48-page table
_BOUNDARIES = (0, 15, 255, 256, 511, 512, 639, 767)
_LATENT_CASES = {'ragged': {}, 'boundaries': dict(at=_BOUNDARIES, maxb=48)}
# where the look-ahead of two blocks crosses from slot to slot (ISSUE 49):
# what lies two blocks on is the slot's own block, the next slot's first,
# its second or — behind a slot of one block — the first of the slot
# after; nothing lies past the last slot's last block
_LATENT_CASES.update({name: dict(at=at, maxb=48) for name, at in {
    'blocks_1_2_3': (100, 300, 600), 'blocks_3_2_1': (600, 300, 100),
    'run_of_one_block_slots': (700, 10, 200, 255, 3, 600, 0, 40, 300),
    'one_slot_one_block': (200,), 'one_slot_three_blocks': (767,),
    'one_block_slots_only': (5, 255, 17),
    'last_slot_one_block': (600, 300, 40),
    'block_edges': (255, 256, 511, 512),
}.items()})


def _latent_case(dtype, at=(0, 15, 300, 511, 639), h=4, w=256, dv=128,
                 bs=16, maxb=40, seed=0):
    rng = np.random.default_rng(seed)
    s = len(at)
    nb = s * maxb + 1
    pool = jnp.asarray(rng.normal(size=(nb, bs, w)), dtype)
    q = jnp.asarray(rng.normal(size=(s, h * w)), jnp.float32)
    pos = jnp.asarray(at, jnp.int32)
    table = jnp.asarray(
        rng.permutation(nb - 1)[:s * maxb].reshape(s, maxb) + 1, jnp.int32)
    return q, pool, pos, table


def _latent_kernel(q, pool, pos, table):
    return np.asarray(ppa.latent_paged_attention(
        q, pool, pos, table, n_head=4, v_width=128, scale=0.07,
        interpret=True))


def _latent_jnp(q, pool, pos, table, h, dv, scale):
    attrs = {'n_head': h, 'n_kv_head': 1, 'v_width': dv, 'scale': scale}
    ctx = types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d))
    with jax.default_matmul_precision('highest'):
        return np.asarray(decode_ops._kv_block_attention_jnp(
            ctx, q, pool, pool, pos, table))


@pytest.mark.parametrize('case', sorted(_LATENT_CASES))
@pytest.mark.parametrize('dtype,tol', [('float32', 1e-5), ('bfloat16', 2e-2)])
def test_latent_kernel_is_the_jnp_body(dtype, tol, case):
    """Interpret mode: one copy of a page, scores over the whole row,
    the sum over its first v_width channels, ragged positions on shuffled
    pages — float32 to rounding, bfloat16 to its operands' rounding (the
    kernel rounds the query and the weights to the pool's dtype)."""
    q, pool, pos, table = _latent_case(dtype, **_LATENT_CASES[case])
    got = _latent_kernel(q, pool, pos, table)
    want = _latent_jnp(q, pool, pos, table, 4, 128, 0.07)
    assert got.shape == (len(pos), 4 * 128) and np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


# sha256 of the interpret-mode output's bytes as the PARENT of PR 44
# (fe973ca: one body for every block, both masks on each) gives them for
# the same case: a full block's rows are all <= pos, the select of every
# row is the identity and sixteen copies are sixteen copies, so the two
# bodies are the one body to the bit
_PARENT_LATENT_SHA256 = {
    ('boundaries', 'bfloat16'): '107ca8fadca9d463',
    ('boundaries', 'float32'): '7c996fcd404c5756',
    ('ragged', 'bfloat16'): '40b30a2e01906550',
    ('ragged', 'float32'): '5f377fe9cab1f6e1',
}


# the same of PR 49's parent (b2f6fb2: two page halves, one block in
# flight, a look-ahead that stops at the slot's edge) on ISSUE 49's cases,
# by that commit's module in interpret mode: a deeper pipeline changes
# WHEN a page arrives, not what is multiplied or in which order
_PARENT_LATENT_SHA256_49 = {
    ('block_edges', 'bfloat16'): 'a2ba166e5844a2c6',
    ('block_edges', 'float32'): 'ee5e1e93d653feb0',
    ('blocks_1_2_3', 'bfloat16'): 'a9b0f4fe0dad9995',
    ('blocks_1_2_3', 'float32'): 'acc1e092c2dbb3ec',
    ('blocks_3_2_1', 'bfloat16'): '49927285853a1b2a',
    ('blocks_3_2_1', 'float32'): '06d466fcb80275f3',
    ('last_slot_one_block', 'bfloat16'): '96c1b5fa63370f5b',
    ('last_slot_one_block', 'float32'): 'ac835b990a620319',
    ('one_block_slots_only', 'bfloat16'): 'b1102a3b58d74a6a',
    ('one_block_slots_only', 'float32'): 'e3dd377cb3115580',
    ('one_slot_one_block', 'bfloat16'): '510dd37ee67842f7',
    ('one_slot_one_block', 'float32'): '72a410cb47a2ac90',
    ('one_slot_three_blocks', 'bfloat16'): '55654205ab3a0a7d',
    ('one_slot_three_blocks', 'float32'): 'ee2666b387778839',
    ('run_of_one_block_slots', 'bfloat16'): 'c76cda7505acc3f9',
    ('run_of_one_block_slots', 'float32'): '9cb11d6e1ccfc0f6',
}


_PARENTS_BITS = {**_PARENT_LATENT_SHA256, **_PARENT_LATENT_SHA256_49}


@pytest.mark.parametrize('case,dtype', sorted(_PARENTS_BITS))
def test_latent_kernels_two_bodies_are_the_parents_one_to_the_bit(case,
                                                                  dtype):
    import hashlib
    got = _latent_kernel(*_latent_case(dtype, **_LATENT_CASES[case]))
    assert hashlib.sha256(got.tobytes()).hexdigest()[:16] == \
        _PARENTS_BITS[case, dtype]


@pytest.mark.parametrize('case', sorted(set(_LATENT_CASES) - {'ragged'}))
@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_nan_past_pos_never_reaches_the_latent_kernels_output(dtype, case):
    """Every pool row no slot attends — the rows past each slot's pos in
    its last page, every page past it and the trash block — set to NaN:
    the output is the clean pool's exactly. A full block has no such row
    (which is why it needs no mask); the last block masks scores and
    values both. With two blocks in flight (ISSUE 49) this also says that
    no half is read for a block other than the one copied into it: a
    page of another slot would bring its NaN rows."""
    q, pool, pos, table = _latent_case(dtype, **_LATENT_CASES[case])
    attended = np.zeros(pool.shape[:2], bool)
    for slot, p in enumerate(np.asarray(pos)):
        for j in range(p + 1):
            attended[table[slot, j // 16], j % 16] = True
    assert not attended[0].any() and not attended.all(1).all()
    poisoned = jnp.where(attended[..., None], pool, jnp.nan)
    got = _latent_kernel(q, poisoned, pos, table)
    assert np.isfinite(got).all()
    assert np.array_equal(got, _latent_kernel(q, pool, pos, table))


def test_the_kernels_say_by_name_what_they_do_not_take():
    f32 = jnp.float32
    q = jax.ShapeDtypeStruct((4, 32 * 576), f32)
    narrow = jax.ShapeDtypeStruct((9, 16, 576), jnp.bfloat16)
    assert 'no multiple of 128' in ppa.refuses(q, narrow, narrow, 32, 1)
    assert 'no multiple of 128' in ppa.refuses_latent(q, narrow, 32, 512)
    wide = jax.ShapeDtypeStruct((9, 16, 640), jnp.bfloat16)
    q640 = jax.ShapeDtypeStruct((4, 32 * 640), f32)
    assert ppa.refuses_latent(q640, wide, 32, 512) is None
    assert 'whole 128-lane tiles' in ppa.refuses_latent(q640, wide, 32, 500)
    assert 'n_head rows' in ppa.refuses_latent(q, wide, 32, 512)
    assert 'sublane' in ppa.refuses_latent(
        q640, jax.ShapeDtypeStruct((9, 8, 640), jnp.bfloat16), 32, 512)
    assert 'float32' in ppa.refuses_latent(
        jax.ShapeDtypeStruct((4, 32 * 640), jnp.bfloat16), wide, 32, 512)
    assert ppa.supports(jax.ShapeDtypeStruct((4, 512), f32),
                        jax.ShapeDtypeStruct((9, 16, 512), f32),
                        jax.ShapeDtypeStruct((9, 16, 512), f32), 8)
    assert ppa.refuses(jax.ShapeDtypeStruct((4, 512), f32),
                       jax.ShapeDtypeStruct((9, 16, 512), f32),
                       jax.ShapeDtypeStruct((9, 16, 512), f32), 8) is None


def _op_ctx(attrs, **names):
    """What a lowering rule sees of an op: its attributes and, where
    `names` gives them, the variables its inputs name."""
    op = types.SimpleNamespace(input=lambda slot: [names[slot]])
    return types.SimpleNamespace(attr=lambda n, d=None: attrs.get(n, d),
                                 **({'op': op} if names else {}))


@pytest.mark.parametrize('attrs,names,error,match', [
    ({'n_head': 4, 'n_kv_head': 1, 'v_width': 128},
     dict(KCache='kv_c_0', VCache='kv_v_0'), ValueError, 'two variables'),
    ({'n_head': 4, 'n_kv_head': 2, 'v_width': 64}, {},
     NotImplementedError, 'ONE K/V head'),
    ({'n_head': 4, 'n_kv_head': 1, 'window': 8, 'v_width': 128}, {},
     NotImplementedError, 'no window'),
    ({'n_head': 4, 'n_kv_head': 1, 'v_width': 256}, {}, ValueError,
     'all value'),
])
def test_the_attribute_alone_says_a_pool_is_latent(attrs, names, error,
                                                   match):
    """v_width set: the op is the latent one whatever the trace hands it,
    and what the attribute promises — one K/V head, no window, KCache and
    VCache the same variable, a value narrower than the row — is refused
    by name, in the step op and the chunk bodies alike (they all read
    _v_width), not answered with another body."""
    q, pool, pos, table = _latent_case('float32')
    ctx = _op_ctx(dict(attrs, scale=0.07), **names)
    with pytest.raises(error, match=match):
        decode_ops._kv_block_attention_jnp(ctx, q, pool, pool, pos, table)
    with pytest.raises(error, match=match):
        decode_ops._chunk_attention_blocked(
            ctx, q[:1, None, :], pool, pool, pos[:1], table[0])


def test_a_latent_op_on_one_variable_is_the_latent_body():
    """The same pool under both slots by NAME (not by object): served."""
    q, pool, pos, table = _latent_case('float32')
    ctx = _op_ctx({'n_head': 4, 'n_kv_head': 1, 'v_width': 128,
                   'scale': 0.07}, KCache='kv_c_0', VCache='kv_c_0')
    ctx.abstract, ctx.tracer = False, types.SimpleNamespace(
        lowered_bodies=[])
    out = decode_ops._kv_block_attention(
        ctx, {'Q': [q], 'KCache': [pool], 'VCache': [pool + 0],
              'Pos': [pos], 'BlockTable': [table]})['Out'][0]
    assert out.shape == (5, 4 * 128)
    assert ctx.tracer.lowered_bodies == [
        ('kv_block_attention', 'latent_kernel')]


def _run_matmul(x, y):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xv = fluid.layers.data('x', list(x.shape), dtype=str(x.dtype),
                               append_batch_size=False)
        yv = fluid.layers.data('y', list(y.shape), dtype=str(y.dtype),
                               append_batch_size=False)
        out = fluid.layers.matmul(xv, yv)
    return fluid.Executor(fluid.CPUPlace()).run(
        main, feed={'x': x, 'y': y}, fetch_list=[out],
        scope=fluid.core.Scope())[0]


def test_matmul_multiplies_a_bfloat16_weight_as_mul_does():
    """matmul's rule since this configuration (mul has had it since PR
    26): a float32 activation over a weight STORED in bfloat16 is rounded
    to bfloat16 and multiplied on the stored bytes with float32 sums —
    the MXU's own product, not an upcast copy of the weight — and comes
    out float32. Every other pairing is jnp.matmul's as it was. Which
    programs meet the rule: q_absorb and v_expand of a latent layer
    (kv_b_w re-viewed per head under the float32 query and result);
    tests/test_decode_ids.py holds the three other decode
    configurations' modules to the parent's by hash, so none of them
    does."""
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 16).astype(np.float32)
    y32 = rng.randn(16, 7).astype(np.float32)
    y16 = jnp.asarray(y32, jnp.bfloat16)
    got = np.asarray(_run_matmul(x, y16))
    assert got.dtype == np.float32
    with jax.default_matmul_precision('highest'):
        rounded = np.asarray(jnp.matmul(
            jnp.asarray(x, jnp.bfloat16).astype(jnp.float32),
            y16.astype(jnp.float32)))
        plain = np.asarray(jnp.matmul(x, y16.astype(jnp.float32)))
    assert np.abs(got - rounded).max() <= 1e-5 * np.abs(rounded).max()
    assert np.abs(got - plain).max() > 1e-4 * np.abs(plain).max()
    np.testing.assert_array_equal(np.asarray(_run_matmul(x, y32)),
                                  np.asarray(jnp.matmul(x, y32)))
    both = _run_matmul(jnp.asarray(x, jnp.bfloat16), y16)
    assert both.dtype == jnp.bfloat16


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)


def test_latent_kernel_compiles_for_a_v5e_at_the_benchmarks_widths(topo):
    """128 slots of 288 pages, 32 heads over a 640-wide bfloat16 row
    whose first 512 channels are the value: the chip's compiler takes
    the kernel (a 576-wide row it refuses: refuses_latent)."""
    import functools
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    s, h, w, dv, bs, maxb = 128, 32, 640, 512, 16, 288
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    fn = jax.jit(functools.partial(ppa.latent_paged_attention, n_head=h,
                                   v_width=dv, scale=192 ** -0.5))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    try:
        compiled = fn.lower(
            sd((s, h * w), jnp.float32),
            sd((s * maxb + 1, bs, w), jnp.bfloat16), sd((s,), jnp.int32),
            sd((s, maxb), jnp.int32)).compile()
    finally:
        jax.config.update('jax_enable_compilation_cache', was)
        cc.reset_cache()
    assert 'kv_block_latent_paged_attention' in compiled.as_text()
