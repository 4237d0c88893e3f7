"""The olmoe_1b_7b configuration and its cell: the file against the
catalog's config, the traffic mix, the bytes a step needs, and the two
readers this cell brings (moe_ffn_device_share, moe_experts_roofline) on a
hand-made timeline and on the recorded v5e trace."""
import importlib
import json
import os
import types

import numpy as np
import pytest

from benchmark import harness, trace, traffic
from benchmark.configs import olmoe_1b_7b as model
from benchmark.layer_metrics import _xplane_meta

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, 'fixture_v5e.xplane.pb')
MS = 1000000
# model-configs catalog, OLMoE-1B-7B-0125-Instruct, `config`
CATALOG = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 1024,
           "max_position_embeddings": 4096, "model_type": "olmoe",
           "norm_topk_prob": False, "num_attention_heads": 16,
           "num_experts": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 16, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "tie_word_embeddings": False, "vocab_size": 50304}


def _cfg():
    return harness.load_json(os.path.join(ROOT, 'benchmark', 'configs',
                                          'olmoe_1b_7b.json'))


def test_every_width_is_the_catalogs_and_depth_is_the_only_cut():
    cfg = _cfg()
    bench = harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    entry = {c['name']: c for c in bench['configs']}['olmoe_1b_7b']
    differs = [k for k, v in CATALOG.items() if cfg[k] != v]
    assert differs == ['num_hidden_layers'] == entry['reduced']
    assert 4 <= cfg['num_hidden_layers'] <= 16
    assert list(cfg['reduced']) == ['num_hidden_layers']
    assert entry['source'] == cfg['source']
    assert cfg['max_cache_len'] == cfg['max_position_embeddings'] == 4096
    assert cfg['max_slots'] >= 32


def test_gen_closed_lengths_fit_the_cache():
    mix = harness.load_json(os.path.join(ROOT, 'benchmark', 'traffic',
                                         'gen_closed.json'))
    assert mix['runner'] == 'decode_closed' and mix['clients'] == 'max_slots'
    rng = traffic.rng_for(2 ** 31 + 5, 0)
    for key in ('prompt_len', 'output_len'):
        xs = traffic.draw_lengths(mix[key], rng, 20000)
        assert xs.min() == 64 and xs.max() == 2048
        assert abs(np.median(xs) - 512) <= 0.04 * 512
    assert mix['prompt_len']['max'] + mix['output_len']['max'] \
        <= _cfg()['max_cache_len']


def test_step_bytes_charge_the_expected_distinct_experts():
    cfg = _cfg()
    # 32 rows x 8 of 64 experts: 64 * (1 - (7/8)^32) = 63.1 distinct
    assert abs(model.expected_distinct_experts(cfg, 32) - 63.107) < 1e-2
    assert model.expected_distinct_experts(cfg, 1) == pytest.approx(8.0)
    one_expert = 3 * 2048 * 1024 * 2
    assert model.moe_expert_bytes(cfg, 32) == pytest.approx(
        cfg['num_hidden_layers'] * 63.107 * one_expert, rel=1e-4)
    assert model.kv_row_bytes(cfg) == 2 * cfg['num_hidden_layers'] * 4096
    dense = model.step_dense_bytes(cfg)
    assert 0.3e9 < dense < 0.45e9        # attention, router, head, norms
    peaks = {'hbm_bytes_per_s': 819e9}
    rows = 33000
    floor = model.step_floor_seconds(cfg, peaks, rows)
    assert floor == pytest.approx(
        (dense + model.moe_expert_bytes(cfg, 32)
         + rows * model.kv_row_bytes(cfg)) / 819e9)
    assert 0.3 < model.moe_expert_bytes(cfg, 32) / (floor * 819e9) < 0.8
    assert model.BOUND == 'memory'


def test_reference_logits_reads_savez_bfloat16_back(tmp_path):
    """np.savez keeps a bfloat16 leaf's bytes under a void dtype: the
    module views it back, and the logits are those of the same weights
    held as bfloat16."""
    import jax.numpy as jnp
    cfg = harness.overlay(_cfg(), _cfg()['rehearsal'])
    d, e, f, v = 64, 8, 32, 128
    rng = np.random.RandomState(0)

    def bf(*shape):
        return np.asarray(jnp.asarray(rng.randn(*shape) * 0.05,
                                      jnp.bfloat16))
    w = {'embed_w': bf(v, d), 'lm_head_w': bf(d, v),
         'final_norm_w': np.ones(d, np.float32)}
    for i in range(2):
        p = 'l%d_' % i
        for n in ('q_w', 'k_w', 'v_w', 'o_w'):
            w[p + n] = bf(d, d)
        for n in ('in_norm_w', 'q_norm_w', 'k_norm_w', 'post_norm_w'):
            w[p + n] = np.ones(d, np.float32)
        w[p + 'moe_router'] = bf(d, e)
        w[p + 'moe_gate'], w[p + 'moe_up'] = bf(e, d, f), bf(e, d, f)
        w[p + 'moe_down'] = bf(e, f, d)
    np.savez(tmp_path / 'w.npz', **w)
    back = dict(np.load(tmp_path / 'w.npz'))
    assert back['embed_w'].dtype.kind == 'V'
    ids = rng.randint(2, v, 20)
    got = np.asarray(model.reference_logits(cfg, back, ids))
    want = np.asarray(model.reference_logits(cfg, w, ids))
    assert got.shape == (20, v) and np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


# -- the two readers ---------------------------------------------------------

def _reader(name):
    return importlib.import_module('benchmark.layer_metrics.' + name).reduce


def _moe_run(monkeypatch, prov, live_rows=32.0):
    """Two 10 ms dispatches of the step, each with 2 ms of attention, 5 ms
    of grouped matmuls, 1 ms of router and 2 ms of the rest, and one
    shorter chunk program between them."""
    ops, mods = [], []
    for t0 in (0, 20):
        mods.append((t0 * MS, (t0 + 10) * MS, 'jit_decode_step(1)'))
        ops += [(t0 * MS, (t0 + 2) * MS, 'attn.1'),
                ((t0 + 2) * MS, (t0 + 3) * MS, 'router.1'),
                ((t0 + 3) * MS, (t0 + 8) * MS, 'ragged.1'),
                ((t0 + 8) * MS, (t0 + 10) * MS, 'head.1')]
    mods.append((12 * MS, 16 * MS, 'jit_prefill_chunk_128(2)'))
    ops.append((12 * MS, 16 * MS, 'ragged.1'))
    dev = trace.Device('/device:TPU:0', ops, mods)
    t = trace.Trace([dev], [], (0, 30 * MS))
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': prov})
    cfg = _cfg()
    return {'trace': t, 'runner': None,
            'result': {'counters_traced': {
                'steps': 2, 'active_slot_steps': int(2 * live_rows)}},
            'ctx': types.SimpleNamespace(
                tracer=types.SimpleNamespace(path='unused'), cfg=cfg,
                model=model, peaks={'hbm_bytes_per_s': 819e9})}


_PROV = {'attn.1': 'jit(decode_step)/kv_block_attention/dot_general:',
         'router.1': 'jit(decode_step)/moe_topk_ffn/router/dot_general:',
         'ragged.1': 'ragged-dot-none:',      # as the chip prints it
         'head.1': 'jit(decode_step)/mul/dot_general:'}


def test_moe_share_and_experts_roofline_on_a_hand_made_timeline(monkeypatch):
    run = _moe_run(monkeypatch, _PROV)
    # router + experts: 6 of the step's 10 ms; the chunk program's time is
    # not the main program's
    assert _reader('moe_ffn_device_share')(run) == pytest.approx(60.0)
    floor = model.moe_expert_bytes(run['ctx'].cfg, 32.0) / 819e9
    assert _reader('moe_experts_roofline')(run) == pytest.approx(
        100.0 * floor / 5e-3)
    # fewer live rows need fewer experts: the share falls with them
    half = _moe_run(monkeypatch, _PROV, live_rows=4.0)
    assert _reader('moe_experts_roofline')(half) \
        < _reader('moe_experts_roofline')(run)


@pytest.mark.parametrize('name', ['moe_ffn_device_share',
                                  'moe_experts_roofline'])
def test_a_program_without_the_scope_gives_the_reader_nothing(monkeypatch,
                                                              name):
    """A program that lacks the op (the parent's, another model's) leaves
    the metric out of the line instead of raising."""
    prov = {k: v.replace('moe_topk_ffn', 'fc') for k, v in _PROV.items()}
    # no moe_topk_ffn op is named: the bare ragged dot is nobody's
    assert _reader(name)(_moe_run(monkeypatch, prov)) is None
    monkeypatch.undo()
    t = trace.load(FIXTURE)
    run = {'trace': t, 'result': {'counters_traced': {
        'steps': 1, 'active_slot_steps': 1}},
        'ctx': types.SimpleNamespace(
            tracer=types.SimpleNamespace(path=FIXTURE), cfg=_cfg(),
            model=model, peaks={'hbm_bytes_per_s': 819e9})}
    assert _reader(name)(run) is None
    no_trace_path = dict(run, ctx=types.SimpleNamespace(
        tracer=types.SimpleNamespace(path=None), cfg=_cfg(), model=model,
        peaks={'hbm_bytes_per_s': 819e9}))
    assert _reader(name)(no_trace_path) is None


def test_the_cell_is_filed_under_every_decode_metric_it_reports():
    bench = harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))
    cell = 'olmoe_1b_7b.gen_closed'
    entry = {w['name']: w for w in bench['workloads']}[cell]
    assert entry == dict(entry, config='olmoe_1b_7b', traffic='gen_closed',
                         chips=1)
    e2e = {m['name'] for m in bench['end_to_end']
           if 'workloads' not in m or cell in m['workloads']}
    # decode_tokens_per_s is not filed: six seeds spread it by 1.9 %, over
    # half its bound (PERF.md 6, PR 26); so nor is what only moves it
    assert e2e == {'itl_p99_ms', 'setup_s'}
    per_layer = {m['name']: m for m in bench['per_layer']
                 if cell in m.get('workloads', ())}
    assert {'moe_ffn_device_share', 'moe_experts_roofline',
            'decode_step_roofline', 'decode_step_device_ms',
            'decode_attention_device_share', 'artifact_load_s',
            'tick_host_ms', 'idle_attributed_share'} <= set(per_layer)
    assert not {'slot_occupancy', 'closed_ttft_p50_ms'} & set(per_layer)
    assert all(m['moves'] in e2e for m in per_layer.values())
    assert json.dumps(bench).count(cell) == 1 + len(per_layer) + 1
