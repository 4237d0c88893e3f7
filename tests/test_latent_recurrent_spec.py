"""The first decode spec with a latent pool (`v_width`) AND recurrent layers
(`recurrent`) in one DecodeSpecBuilder (models/kimi_linear.py): what each
layer keeps, the pools' bytes by kind, the export's state list and its
birth program, and what is refused on it BY NAME — prefix reuse, beams, a
verify program, an int8 pool. Every accepted spec's StableHLO is pinned in
tests/test_decode_ids.py, which this PR leaves as it is."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import DecodingPredictor, decoding, export_decode
from models.decode_spec import DecodeSpecBuilder
from models.kimi_linear import KDA, MLA, build_decode_spec, layer_types

TOY = dict(vocab=128, d_model=64, n_layer=8, full_attn_layers=(4, 8),
           kda_heads=2, kda_head_dim=16, n_head=4, kv_lora_rank=32,
           d_nope=16, d_rope=8, d_v=16, d_dense=96, n_expert=16, n_held=4,
           expert_offset=4, d_expert=32, top_k=4, max_slots=4,
           max_cache_len=96, block_size=8, chunk_sizes=(8, 16),
           weights_dtype='float32', kv_cache_dtype='float32')
KINDS = layer_types(8, (4, 8))
REC = [i for i, t in enumerate(KINDS) if t == KDA]
LAT = [i for i, t in enumerate(KINDS) if t == MLA]


@pytest.fixture(scope='module')
def exported(tmp_path_factory):
    art = str(tmp_path_factory.mktemp('latent_recurrent') / 'art')
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(**TOY)
        spec['startup'].random_seed = 11
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        export_decode(spec, art, scope=scope, precompile=False)
    with open(os.path.join(art, decoding._DECODE_SIGNATURE)) as f:
        sig = json.load(f)
    return art, spec, sig


def _builder(**kw):
    args = dict(vocab=16, d_model=8, kv_width=128, n_layer=4, max_slots=2,
                max_cache_len=16, block_size=4, chunk_sizes=(4,),
                num_blocks=None, eos_id=1, kv_cache_dtype='float32',
                v_width=32, recurrent={0: {'state': ([2, 4, 4], 'float32')},
                                       2: {'state': ([2, 4, 4], 'float32'),
                                           'conv': ([3, 8], 'float32')}})
    return DecodeSpecBuilder(**dict(args, **kw))


def test_each_layer_keeps_its_own_kind():
    """`cache_names`: a recurrent layer its per-slot states, every other
    layer ONE latent pool — never a K and a V pool."""
    b = _builder()
    assert [b.cache_names(i) for i in range(4)] == [
        ['rec_state_0'], ['kv_c_1'], ['rec_state_2', 'rec_conv_2'],
        ['kv_c_3']]
    with pytest.raises(ValueError, match='layer 1 keeps no recurrent state'):
        b.state(1)
    with pytest.raises(ValueError, match='layer 0 keeps 1 pool'):
        b._io = {'write': None}
        b.write(0, None, None)


def test_the_spec_names_both_kinds(exported):
    _, spec, _ = exported
    assert KINDS == [KDA, KDA, KDA, MLA] * 2
    assert spec['cache_kind'] == 'latent'
    assert spec['cache_vars'] == [
        n for i in range(8) for n in (
            ['kv_c_%d' % i] if i in LAT
            else ['rec_state_%d' % i, 'rec_conv_%d' % i])]
    assert spec['recurrent']['cache_vars'] == [
        n for i in REC for n in ('rec_state_%d' % i, 'rec_conv_%d' % i)]
    # recurrent layers: no row program; one table feed and a state_slot
    assert 'chunk_rows' not in spec and 'window' not in spec
    for size, prog in spec['chunk'].items():
        assert prog['feeds'] == ['chunk_ids', 'start', 'chunk_len',
                                 'block_table', 'state_slot']
    assert spec['step']['feeds'] == ['tokens', 'pos', 'block_tables']
    # the latent ops are told where in the row the value lies
    for prog in [spec['step']] + list(spec['chunk'].values()):
        attend = [op for op in prog['program'].global_block().ops
                  if 'attention' in op.type]
        assert len(attend) == len(LAT)
        assert {(op.attr('n_kv_head'), op.attr('v_width'))
                for op in attend} == {(1, 32)}


def test_the_exports_state_list_and_birth(exported):
    """The signature's state: every pool and every per-slot state, then the
    ids row; `block` says which are recurrent and that a row is latent;
    decode_zeros/ births them all."""
    art, spec, sig = exported
    names = [e['name'] for e in sig['state']]
    assert names[:-1] == spec['cache_vars']
    shapes = {e['name']: tuple(e['shape']) for e in sig['state']}
    assert shapes['kv_c_3'] == (4 * 12 + 1, 8, 128)
    assert shapes['rec_state_0'] == (4, 2, 16, 16)
    assert shapes['rec_conv_0'] == (4, 3, 96)
    assert sig['block']['cache_kind'] == 'latent'
    assert sig['block']['recurrent']['cache_vars'] \
        == spec['recurrent']['cache_vars']
    assert os.path.exists(os.path.join(art, decoding._ZEROS_DIR,
                                       'module.jaxexport'))
    row, pools = decoding.pool_facts(sig)
    assert row == 2 * 128 * 4       # two latent layers, float32 rows
    assert pools == {
        'latent': 2 * (4 * 12 + 1) * 8 * 128 * 4,
        'recurrent': 6 * 4 * 4 * (2 * 16 * 16 + 3 * 96)}


def test_prefix_reuse_beams_and_a_drafter_are_refused_by_name(exported):
    art = exported[0]
    with DecodingPredictor(art) as pred:
        snap = pred.stats.snapshot()
        assert snap['pool_bytes'] == decoding.pool_facts(exported[2])[1]
        assert snap['recurrent_state_bytes'] \
            == snap['pool_bytes']['recurrent']
        assert pred.attention_bodies['step']['kv_block_attention'] \
            == {'latent_jnp': len(LAT)}
        tokens = np.arange(2, 40)
        with pytest.raises(ValueError, match='prefix reuse is refused on a '
                           'cache with recurrent layers'):
            pred.block_manager.match_prefix(tokens)
        with pytest.raises(ValueError, match='recurrent layers'):
            pred.block_manager.register_prefix(tokens, [1, 2, 3, 4])
        with pytest.raises(ValueError, match='beam search is refused on an '
                           'artifact with recurrent layers'):
            pred.submit(tokens, max_new_tokens=4, beam=2).result(60)
        # greedy requests are what it serves
        served = pred.generate(tokens, max_new_tokens=3, timeout=120)
        assert 1 <= len(served) <= 3     # eos_id 1 may end it early
    with pytest.raises(ValueError, match='verify program'):
        DecodingPredictor(art, draft='ngram')


def test_a_verify_program_and_a_row_program_are_refused_at_export(tmp_path):
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = build_decode_spec(**TOY)
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
    (dict(kv_cache_dtype='int8'), 'window layers|a latent pool'),
    (dict(draft_k=2), 'a latent pool'),
    (dict(window_layers=[0], window=4), 'recurrent or a window layer'),
    (dict(shared_pools={2: 1}), 'cannot attend layer'),
    (dict(v_width=256), 'v_width must be in')])
def test_the_builder_refuses_by_the_kinds_name(over, said):
    with pytest.raises(ValueError, match=said):
        _builder(**over)
