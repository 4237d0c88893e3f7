"""The kimi_linear_48b_a3b configuration and its cell: the file against the
catalog's config (three keys reduced), what it assumes, the accepted
longgen_closed mix against the cache, the parameters held (3,450,547,008:
6.90 GB of bfloat16) and the bytes a step, its latent attention, its routed
layer and its Kimi Delta Attention STATE need, the chunked rule's operations
and bytes, against ISSUE 57's hand counts; the two readers this cell brings
(kda_chunk_roofline, slice_carried_share) on hand-made timelines and tick
logs and on a program that lacks what they read; the cell's entries in
BENCHMARK.json BY NAME — what THIS PR filed, as a subset: no count of cells
or configurations is pinned — and the cell end to end under --rehearsal,
tracing off and on."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, trace, traffic
from benchmark.configs import kimi_linear_48b_a3b as model
from benchmark.layer_metrics import (_xplane_meta, kda_chunk_roofline,
                                     linear_attention_roofline,
                                     slice_carried_share)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CONFIG = 'kimi_linear_48b_a3b'
CELL = CONFIG + '.longgen_closed'
MS = 1000000
# model-configs catalog, Kimi-Linear-48B-A3B-Instruct, `config`
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {'num_hidden_layers': 13, 'num_experts': 32, 'vocab_size': 20480}
# the accepted per-layer metrics this PR appended the cell to, by name
JOINED = {'decode_step_device_ms', 'decode_step_roofline', 'artifact_load_s',
          'tick_host_ms', 'tick_feed_ms', 'tick_d2h_ms', 'tick_advance_ms',
          'tick_admit_ms', 'step_d2h_bytes', 'idle_attributed_share',
          'decode_attention_device_share', 'decode_attention_roofline',
          'moe_ffn_device_share', 'moe_experts_roofline',
          'latent_proj_device_share', 'linear_attention_device_share',
          'linear_attention_roofline', 'linear_attention_slice_device_ms',
          'step_ahead_share', 'feed_touched_share', 'sched_offcpu_share',
          'tick_gc_share', 'tick_ms_p99', 'tick_ms_max',
          'slices_per_chunk_dispatch', 'prefill_slice_device_ms',
          'slice_deferred_share', 'emit_gap_ms_p99',
          'gap_p99_prefill_tokens', 'gap_p99_wait_share',
          'slice_read_wait_ms', 'request_ttft_p95_ms'}
NEW = {'kda_chunk_roofline': ('%', 'device_trace', 'Op lowerings / kernels'),
       'slice_carried_share': ('%', 'program_counter', 'Decode scheduler')}
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}


def _cfg():
    return harness.load_json(os.path.join(ROOT, 'benchmark', 'configs',
                                          CONFIG + '.json'))


def _bench():
    return harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))


def test_every_key_is_the_catalogs_but_the_three_reduced():
    cfg = _cfg()
    assert {k: cfg[k] for k in CATALOG} == dict(CATALOG, **REDUCED)
    entry = {c['name']: c for c in _bench()['configs']}[CONFIG]
    assert sorted(entry['reduced']) == sorted(REDUCED) == sorted(
        cfg['reduced'])
    assert entry['file'] == 'benchmark/configs/%s.json' % CONFIG
    assert entry['source'] == cfg['source'] and len(entry['why']) <= 200
    assert 'KDA 3 : MLA 1' in entry['why']
    assert {k: cfg['published'][k] for k in REDUCED} == {
        k: CATALOG[k] for k in REDUCED}
    assert '8 chips share each layer' in cfg['deployment']
    assert 'thirteen layers a pipeline stage' in cfg['deployment']
    assert (cfg['num_experts_routed'], cfg['expert_offset']) == (256, 0)
    for key in ('equations', 'block', 'kda', 'decay', 'recurrent_state',
                'mla', 'router', 'router_bias', 'conv_weights', 'embed_std',
                'eos_id', 'max_cache_len', 'max_slots', 'num_blocks'):
        assert key in cfg['assumed']
    assert (cfg['max_slots'], cfg['block_size'], cfg['max_cache_len'],
            cfg['chunk_sizes'], cfg['eos_id']) == (96, 16, 8192, [128, 512],
                                                   1)
    assert cfg['kv_cache_dtype'] == cfg['weights_dtype'] == 'bfloat16'
    assert cfg['state_dtype'] == 'float32'


def test_the_layer_map_is_the_published_lists_first_thirteen():
    cfg = _cfg()
    from models.kimi_linear import KDA, MLA, layer_types
    types_ = layer_types(13, cfg['linear_attn_config']['full_attn_layers'])
    assert [i for i, t in enumerate(types_) if t == MLA] == [3, 7, 11]
    assert types_.count(KDA) == 10 and model._layers(cfg) == (10, 3)
    # the published list, whole: 20 KDA and 7 MLA layers of 27
    whole = layer_types(27, CATALOG['linear_attn_config']['full_attn_layers'])
    assert [i + 1 for i, t in enumerate(whole) if t == KDA] \
        == CATALOG['linear_attn_config']['kda_layers']
    assert model._kda(cfg) == (32, 128, 4)


def test_longgen_closed_lengths_fit_the_cache_and_take_several_slices():
    cfg = _cfg()
    mix = harness.load_json(os.path.join(ROOT, 'benchmark', 'traffic',
                                         'longgen_closed.json'))
    assert mix['runner'] == 'decode_closed' and mix['clients'] == 'max_slots'
    assert (mix['prompt_len']['max'] + mix['output_len']['max']
            <= cfg['max_cache_len'])
    # a prompt is 1-8 slices of the largest chunk program
    assert -(-mix['prompt_len']['max'] // max(model.chunk_sizes(cfg))) == 8
    v = cfg['verify']
    assert max(v['prompt_lens']) + v['max_new_tokens'] <= v['pad_to'] \
        <= cfg['max_cache_len']
    lens = v['prompt_lens']
    assert len(lens) <= cfg['max_slots'] and v['max_new_tokens'] == 48
    # 1, 5 and 12 slices, and a sample of the traffic's own lengths
    assert lens[:4] == [24, 300, 2100, 6000]
    assert [-(-n // 512) for n in (300, 2100, 6000)] == [1, 5, 12]
    assert all(mix['prompt_len']['min'] <= n <= mix['prompt_len']['max']
               for n in lens[4:]) and len(lens[4:]) >= 16
    assert 0 < v['margin_eps'] and 0 < v['routing_gap_eps']
    # the generator under a seed past 32 bits: ids in the slice held
    reqs = traffic.closed_requests(mix, 2 ** 31 + 42, 95,
                                   model.vocab_size(cfg))
    drawn = [next(reqs) for _ in range(96)]
    assert all(256 <= len(p) <= 4096 and 128 <= n <= 4096 for p, n in drawn)
    assert all(2 <= p.min() and p.max() < 20480 for p, _ in drawn)
    slices = [-(-len(p) // 512) for p, _ in drawn]
    assert 3.5 < np.mean(slices) < 5.5


def test_parameters_against_issue_57s_table():
    cfg = _cfg()
    kda = (3 * 2304 * 4096 + 3 * 4096 * 4 + 2304 * 128 + 128 * 4096
           + 2304 * 32 + 32 + 4096 + 2304 * 128 + 128 * 4096 + 128
           + 4096 * 2304)
    assert model.kda_params(cfg) == kda == 39514272
    mla = 2304 * 6144 + 2304 * 576 + 512 + 512 * 8192 + 4096 * 2304
    assert model.mla_params(cfg) == mla == 29114880
    assert model.expert_params(cfg) == 7077888
    routed = 32 * 7077888 + 7077888 + 589824 + 256 + 4608
    assert routed == 234164992
    first = kda + 3 * 2304 * 9216 + 4608
    assert first == 103219872
    assert (kda + routed, mla + routed) == (273679264, 263279872)
    table = 2 * 20480 * 2304 + 2304
    assert table == 94374144
    held = first + 9 * (kda + routed) + 3 * (mla + routed) + table
    assert model.weight_params(cfg) == held == 3450547008
    assert 2 * held == pytest.approx(6.90e9, rel=1e-3)


def test_bytes_against_issue_57s_arithmetic():
    cfg = _cfg()
    # a KDA layer's state [32, 128, 128] float32 + a [3, 12288] float32 tail
    assert model.state_slot_bytes(cfg) == 2097152 + 147456 == 2244608
    assert model.linear_state_bytes(cfg, 1) == 2 * 10 * 2244608
    assert model.linear_state_bytes(cfg, 96) == pytest.approx(4.31e9,
                                                              rel=1e-3)
    # 576 values of bfloat16 a cached row a MLA layer; the pool stores 640
    assert model.kv_row_bytes(cfg) == 1152
    rows = 96 * 2900
    assert model.attention_bytes(cfg, rows, 96) == 3 * 1152 * rows
    assert model.attention_bytes(cfg, rows, 96) == pytest.approx(0.96e9,
                                                                 rel=5e-3)
    # 30.4 of 32 held experts touched at 96 rows, 14.16 MB each, 12 layers
    assert model.expected_distinct_experts(cfg, 96) == pytest.approx(30.4,
                                                                     abs=0.1)
    assert model.moe_expert_bytes(cfg, 96) == pytest.approx(
        12 * model.expected_distinct_experts(cfg, 96) * 14155776)
    assert model.moe_expert_bytes(cfg, 96) == pytest.approx(5.17e9,
                                                            rel=2e-3)
    # the KDA projections 0.79 GB, the rest of the dense weights 0.58
    assert 2 * 10 * model.kda_proj_params(cfg) == pytest.approx(0.79e9,
                                                                rel=5e-3)
    assert model.step_dense_bytes(cfg) - 2 * 10 * model.kda_proj_params(cfg) \
        == pytest.approx(0.58e9, rel=5e-3)
    need = model.step_needed_bytes(cfg, rows)
    assert need == pytest.approx(11.8e9, rel=3e-3)
    assert model.step_floor_seconds(cfg, PEAKS, rows) == pytest.approx(
        14.4e-3, rel=3e-3)
    assert model.linear_state_bytes(cfg, 96) / need == pytest.approx(
        0.36, abs=0.01)
    assert model.moe_expert_bytes(cfg, 96) / need == pytest.approx(0.44,
                                                                   abs=0.01)
    assert model.BOUND == 'memory'


def test_the_chips_resident_bytes_are_what_the_file_says():
    """12.07 GB of 17.18: the weights, ten layers' states at 96 slots, three
    latent pools stored 640 wide."""
    cfg = _cfg()
    state = 10 * 96 * model.state_slot_bytes(cfg)
    pool = 3 * (96 * 512 + 1) * 16 * 1280
    assert (state, pool) == (2154823680, 3019960320)
    assert 2 * model.weight_params(cfg) + state + pool == pytest.approx(
        12.07e9, rel=1e-3)


def test_the_chunked_rules_operations_and_bytes_against_hand_counts():
    cfg = _cfg()
    # a sub-chunk of 64 tokens, a head of 128 x 128
    sub = (4 * 64 * 64 * 128 + 64 * 64 * 256 + 2 * 64 * 64 * 128
           + 6 * 64 * 128 * 128)
    assert sub == 8 * 64 * 64 * 128 + 6 * 64 * 128 * 128 == 10485760
    assert model.kda_chunk_flops(cfg, 512) == 10 * 32 * 8 * sub
    assert model.kda_chunk_flops(cfg, 512) / 10 == pytest.approx(2.68e9,
                                                                 rel=2e-3)
    # 330 real tokens: five sub-chunks of 64 and one of 10, not eight of 64
    rest = 8 * 10 * 10 * 128 + 6 * 10 * 128 * 128
    assert model.kda_chunk_flops(cfg, 330) == 10 * 32 * (5 * sub + rest)
    assert model.kda_chunk_flops(cfg, 0) == 0
    # bytes: the state once read and written; q, k, v, g, o [tokens, 4096]
    # and beta [tokens, 32], float32
    assert model.kda_chunk_bytes(cfg, 512) == 10 * (
        2 * 2097152 + 512 * (5 * 4096 + 32) * 4)
    assert model.kda_chunk_bytes(cfg, 512) / 10 == pytest.approx(46.2e6,
                                                                 rel=2e-3)
    assert model.kda_chunk_bytes(cfg, 0) == 10 * 2 * 2097152
    # the bytes bound the chunk on this chip, at any length
    for tokens in (64, 330, 445.5, 512):
        assert (model.kda_chunk_bytes(cfg, tokens) / 819e9
                > model.kda_chunk_flops(cfg, tokens) / 197e12)


# -- the readers ---------------------------------------------------------------
def _timeline(modules, ops=()):
    dev = trace.Device('/device:TPU:0', ops=list(ops), modules=list(modules))
    return trace.Trace(devices=[dev], host=[], window=(0, 200 * MS))


def _requests(prompt_lens, slices, t=1.0):
    rows = np.zeros(len(prompt_lens), dtype=[
        ('t_submit', 'f8'), ('t_first', 'f8'), ('prompt_len', 'i8'),
        ('prefix_covered', 'i8'), ('slices', 'i8')])
    rows['t_submit'], rows['t_first'] = t, t + 0.1
    rows['prompt_len'], rows['slices'] = prompt_lens, slices
    return rows


def _ticks(slices, carried=None, t0=1.0):
    names = ['t0', 'wall_s', 'slices'] + ['slices_carried'] * (
        carried is not None)
    rows = np.zeros(len(slices), dtype=[(n, 'f8') for n in names])
    rows['t0'] = t0 + 0.01 * np.arange(len(slices))
    rows['slices'] = slices
    if carried is not None:
        rows['slices_carried'] = carried
    return rows


def _run(tr, path='x', live=96, requests=None, ticks=None, mod=model):
    ctx = types.SimpleNamespace(model=mod, cfg=_cfg(),
                                tracer=types.SimpleNamespace(path=path),
                                peaks=PEAKS)
    stats = types.SimpleNamespace()
    if requests is not None:
        stats.request_log = (
            lambda since=0.0: requests[requests['t_submit'] >= since])
    if ticks is not None:
        stats.tick_log = lambda since=None: (
            ticks if since is None else ticks[ticks['t0'] >= since])
    runner = types.SimpleNamespace(served=types.SimpleNamespace(
        pred=types.SimpleNamespace(stats=stats)))
    return {'trace': tr, 'ctx': ctx, 'runner': runner,
            'result': {'floor_arg': 96 * 2900, 't_open': 0.5,
                       'window_s': 45.0,
                       'counters_traced': {'steps': 10,
                                           'active_slot_steps': 10 * live}}}


NAMES = {
    'i': 'jit(decode_step)/linear_attention/in_proj/mul/dot_general',
    's': 'jit(decode_step)/linear_attention/delta_rule/gated_delta_step/'
         'pallas_call',
    'f': 'jit(decode_step)/mul/dot_general',
    'C': 'jit(prefill_chunk_512)/linear_attention/delta_rule/'
         'gated_delta_chunk/dot_general',
    'E': 'jit(prefill_chunk_512)/linear_attention/delta_rule/'
         'gated_delta_chunk/exp',
    'V': 'jit(prefill_chunk_512)/linear_attention/conv/causal_conv_chunk/mul',
    'F': 'jit(prefill_chunk_512)/mul/dot_general',
    'S': 'jit(prefill_chunk_128)/linear_attention/delta_rule/'
         'gated_delta_chunk/dot_general'}


def _step(t0):
    cuts = [0, 2, 8, 20]
    return [((t0 + a) * MS, (t0 + b) * MS, n)
            for a, b, n in zip(cuts, cuts[1:], 'isf')]


def _slice(t0, name='jit_prefill_chunk_512(1)'):
    ops = [(t0 * MS, (t0 + 8) * MS, 'C'),
           ((t0 + 8) * MS, (t0 + 10) * MS, 'E'),
           ((t0 + 10) * MS, (t0 + 11) * MS, 'V'),
           ((t0 + 11) * MS, (t0 + 35) * MS, 'F')]
    return ops, (t0 * MS, (t0 + 35) * MS, name)


def test_kda_chunk_roofline_on_a_hand_made_timeline(monkeypatch):
    """Four steps of 20 ms (6 under the rule) and two 512-slices of 35 ms —
    8 + 2 under the chunked rule, 1 the convolution, which is NOT the
    rule's — and a 128-slice the reader of the largest program does not
    count."""
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': NAMES})
    a, ma = _slice(40)
    b, mb = _slice(80)
    at = (0, 20, 120, 140)
    ops = sum((_step(t) for t in at), []) + a + b + [
        (160 * MS, 180 * MS, 'S')]
    mods = [(t * MS, (t + 20) * MS, 'jit_decode_step(4)') for t in at] + [
                ma, mb, (160 * MS, 181 * MS, 'jit_prefill_chunk_128(1)')]
    cfg = _cfg()
    # 32 requests of 2,048 tokens in 5 slices: 409.6 real tokens a slice
    run = _run(_timeline(mods, ops), requests=_requests([2048] * 32, 5))
    floor = model.kda_chunk_bytes(cfg, 409.6) / 819e9
    assert kda_chunk_roofline.reduce(run) == pytest.approx(
        100 * floor / 10e-3)
    assert kda_chunk_roofline.reduce(run) == pytest.approx(4.62, abs=0.01)
    # the step's rule is the accepted reader's, by the same scope
    assert linear_attention_roofline.reduce(run) == pytest.approx(
        100 * model.linear_state_bytes(cfg, 96) / 819e9 / 6e-3)
    # full slices are dearer; a rule bound by its operations is read by them
    full = _run(_timeline(mods, ops), requests=_requests([2048] * 32, 4))
    assert kda_chunk_roofline.reduce(full) == pytest.approx(
        100 * model.kda_chunk_bytes(cfg, 512) / 819e9 / 10e-3)
    run['ctx'].peaks = dict(PEAKS, bf16_flops_per_s=1e12)
    assert kda_chunk_roofline.reduce(run) == pytest.approx(
        100 * model.kda_chunk_flops(cfg, 409.6) / 1e12 / 10e-3)


def test_slice_carried_share_on_a_hand_made_tick_log():
    # 10 slices, 7 of them past their prompt's first token
    ticks = _ticks([1, 0, 2, 1, 3, 0, 3], [0, 0, 1, 1, 3, 0, 2])
    assert slice_carried_share.reduce(_run(None, ticks=ticks)) \
        == pytest.approx(70.0)
    # every prompt one slice: the reason_closed cells
    assert slice_carried_share.reduce(
        _run(None, ticks=_ticks([1, 1, 1], [0, 0, 0]))) == 0.0
    # ticks before the window opened are not the window's
    early = np.concatenate([_ticks([4, 4], [4, 4], t0=0.1), ticks])
    assert slice_carried_share.reduce(_run(None, ticks=early)) \
        == pytest.approx(70.0)


def test_the_readers_find_nothing_where_there_is_nothing(monkeypatch):
    """A program without the scope, the column or the logs (the parent's,
    any other configuration's), a window without a slice, a configuration
    without the functions, a run without a trace: None, never an error."""
    a, ma = _slice(0)
    tl = _timeline([ma], a)
    reqs = _requests([2048] * 8, 5)
    other = {k: v.replace('linear_attention', 'state_space')
             for k, v in NAMES.items()}
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': other})
    empty = trace.Trace(devices=[], host=[], window=(0, MS))
    assert kda_chunk_roofline.reduce(_run(tl, requests=reqs)) is None
    assert kda_chunk_roofline.reduce(_run(empty, requests=reqs)) is None
    assert kda_chunk_roofline.reduce(_run(None, requests=reqs)) is None
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': NAMES})
    assert kda_chunk_roofline.reduce(_run(tl, requests=reqs)) > 0
    assert kda_chunk_roofline.reduce(_run(tl)) is None       # no request log
    steps = _timeline([(0, 20 * MS, 'jit_decode_step(4)')], _step(0))
    assert kda_chunk_roofline.reduce(_run(steps, requests=reqs)) is None
    lacking = types.SimpleNamespace(chunk_sizes=model.chunk_sizes)
    assert kda_chunk_roofline.reduce(
        _run(tl, requests=reqs, mod=lacking)) is None
    # the parent's tick log has no such column; no log; no slice
    assert slice_carried_share.reduce(
        _run(None, ticks=_ticks([1, 2, 3]))) is None
    assert slice_carried_share.reduce(_run(None)) is None
    assert slice_carried_share.reduce(
        _run(None, ticks=_ticks([0, 0], [0, 0]))) is None


def test_ways_at_takes_near_ties_every_way_and_farther_ones_alone():
    """reference_ways' rule on a hand-made record of sides (reference_sides
    makes it on the chip; chip_smoke.py phase L reads it at several gaps):
    ties within the gap combine, ties up to twice as far stand alone, ties
    farther are not looked at, a row whose sides did not fit is undecided."""
    lg = np.zeros((4, 5), np.float32)
    side = lambda k: np.eye(5, dtype=np.float32)[k]
    alt = {'row': np.array([2, 2, 2, 3]),
           'dist': np.array([0.01, 0.05, 0.2, 0.01], np.float32),
           'logits': np.stack([side(0), side(1), side(2), side(3)]),
           'overflow': [1]}
    ways = model.ways_at(lg, alt, 0.03)
    assert ways[1] is None and sorted(ways) == [1, 2, 3]
    np.testing.assert_array_equal(ways[2], [lg[2], side(0), side(1)])
    np.testing.assert_array_equal(ways[3], [lg[3], side(3)])
    assert model.ways_at(lg, alt, 0.0) == {1: None}
    wide = model.ways_at(lg, alt, 0.1)[2]
    np.testing.assert_array_equal(
        wide, [lg[2], side(0), side(1), side(0) + side(1), side(2)])


def test_the_cell_is_filed_by_name_under_what_it_reports():
    """What THIS PR filed, as a subset of what the cell reports: a later PR
    that files one more metric, cell or configuration breaks nothing
    here."""
    bench = _bench()
    cells = {w['name']: w for w in bench['workloads']}
    assert cells[CELL] == dict(cells[CELL], config=CONFIG,
                               traffic='longgen_closed', chips=1)
    assert len(cells[CELL]['why']) <= 200
    e2e = {m['name'] for m in bench['end_to_end']
           if 'workloads' not in m or CELL in m['workloads']}
    assert {'itl_p99_ms', 'setup_s'} <= e2e
    by_name = {m['name']: m for m in bench['per_layer']}
    filed = {n for n, m in by_name.items() if CELL in m.get('workloads', ())}
    assert JOINED | set(NEW) <= filed
    for name, (unit, source, layer) in NEW.items():
        assert by_name[name]['workloads'][0] == CELL
        assert (by_name[name]['unit'], by_name[name]['source'],
                by_name[name]['layer']) == (unit, source, layer)
    for name in JOINED | set(NEW):
        assert by_name[name]['moves'] == (
            'setup_s' if name == 'artifact_load_s' else 'itl_p99_ms')
        assert os.path.exists(os.path.join(
            ROOT, 'benchmark', 'layer_metrics', name + '.py'))
    for name in JOINED:
        # appended: the accepted cells before it are as they were
        before = by_name[name]['workloads'][
            :by_name[name]['workloads'].index(CELL)]
        assert before and CELL not in before
    assert not any(n.startswith(('ssm_', 'shared_cache_', 'tick_window'))
                   for n in filed)
    # the accepted traffic file, byte for byte
    mix = os.path.join('benchmark', 'traffic', 'longgen_closed.json')
    diff = subprocess.run(['git', 'diff', '--quiet', 'HEAD', '--', mix],
                          cwd=ROOT)
    assert diff.returncode in (0, 129)


@pytest.mark.parametrize('traced', [0, 1])
def test_the_cell_runs_end_to_end_under_rehearsal(traced):
    """The harness finds the configuration, the traffic mix and the new
    readers by name and runs the cell at toy sizes on the cpu: a clean
    window, transcripts the token-by-token reference agrees with, prompts
    of several slices served through per-slot KDA states and latent
    pools."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', CELL, '--seed', str(2 ** 31 + 42), '--seconds', '4',
         '--trace', str(traced), '--rehearsal'],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line['rehearsal'] is True and line['correct'] is False
    assert line['rehearsal_checks_passed'] is True, out.stdout[-3000:]
    assert line['failed'] == 0 and line['attempted'] > 0
    metrics = line['metrics']
    if not traced:
        assert {'itl_p99_ms', 'setup_s'} <= set(metrics)
        assert metrics['itl_p99_ms']['value'] > 0
        return
    assert metrics['compiles_in_window']['value'] == 0
    assert metrics['prefill_slice_device_ms']['value'] > 0
    assert metrics['step_d2h_bytes']['value'] == 4 * 8      # ids alone
    # the rehearsal's prompts of 8-120 tokens take up to 8 slices of 16
    assert metrics['slice_carried_share']['value'] > 30
    # the cpu's trace names no scope: the scope readers are left out
    assert not {'kda_chunk_roofline', 'linear_attention_roofline',
                'latent_proj_device_share'} & set(metrics)
