"""The joyai_llm_flash configuration and its cell: the file against the
catalog's config, the share it states, the traffic mix against the cache,
the bytes and operations a step, its routed feed-forward and its latent
attention need (against hand counts), the two readers this cell brings
(latent_proj_device_share, prefill_slice_device_ms) on hand-made timelines
and on a program that lacks what they read, the cell's entries in
BENCHMARK.json BY NAME, and the cell end to end under --rehearsal."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import harness, trace, traffic
from benchmark.configs import joyai_llm_flash as model
from benchmark.layer_metrics import (_xplane_meta, latent_proj_device_share,
                                     prefill_slice_device_ms)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = 'joyai_llm_flash.reason_closed'
MS = 1000000
# model-configs catalog, JoyAI-LLM-Flash, `config`
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}
REDUCED = ['num_hidden_layers', 'n_routed_experts', 'vocab_size',
           'num_nextn_predict_layers']
# the per-layer metrics the cell is filed under, by name
SHARED = {'decode_step_device_ms', 'decode_step_roofline', 'artifact_load_s',
          'tick_host_ms', 'tick_feed_ms', 'tick_d2h_ms', 'tick_advance_ms',
          'tick_admit_ms', 'step_d2h_bytes', 'idle_attributed_share',
          'decode_attention_device_share', 'moe_ffn_device_share',
          'moe_experts_roofline', 'decode_attention_roofline',
          'step_ahead_share', 'feed_touched_share', 'sched_offcpu_share',
          'tick_gc_share', 'tick_ms_p99', 'tick_ms_max',
          'slices_per_chunk_dispatch'}
NEW = {'latent_proj_device_share', 'prefill_slice_device_ms'}


def _cfg():
    return harness.load_json(os.path.join(ROOT, 'benchmark', 'configs',
                                          'joyai_llm_flash.json'))


def _bench():
    return harness.load_json(os.path.join(ROOT, 'BENCHMARK.json'))


def test_every_width_is_the_catalogs_and_the_cuts_are_the_four_stated():
    cfg = _cfg()
    differs = sorted(k for k, v in CATALOG.items() if cfg[k] != v)
    assert differs == sorted(REDUCED)
    entry = {c['name']: c for c in _bench()['configs']}['joyai_llm_flash']
    assert entry['reduced'] == REDUCED and entry['file'] == (
        'benchmark/configs/joyai_llm_flash.json')
    assert entry['source'] == cfg['source'] and len(entry['why']) <= 200
    assert sorted(cfg['reduced']) == sorted(REDUCED)
    assert {k: cfg['published'][k] for k in REDUCED} == {
        k: CATALOG[k] for k in REDUCED}
    # the share: 32 of 256 experts, an eighth of the vocabulary, 11 layers
    assert cfg['n_routed_experts'] * 8 == cfg['n_experts_routed'] == 256
    assert cfg['vocab_size'] * 8 == 129280
    assert cfg['num_hidden_layers'] == 11 >= 1 + 4
    assert cfg['num_nextn_predict_layers'] == 0
    assert '8 chips share each layer' in cfg['deployment']
    for key in ('latent_attention', 'rotary', 'cache_row', 'absorbed',
                'router'):
        assert key in cfg['assumed']
    assert not any(k.endswith(('_dim', '_rank')) for k in REDUCED)


def test_reason_closed_lengths_fit_the_cache_and_one_slice():
    cfg = _cfg()
    mix = harness.load_json(os.path.join(ROOT, 'benchmark', 'traffic',
                                         'reason_closed.json'))
    assert mix['runner'] == 'decode_closed' and mix['clients'] == 'max_slots'
    assert mix['prompt_len'] == {'dist': 'lognormal', 'median': 320,
                                 'sigma': 0.35, 'min': 160, 'max': 512}
    assert mix['output_len'] == {'dist': 'lognormal', 'median': 2048,
                                 'sigma': 0.5, 'min': 512, 'max': 4096}
    assert mix['ramp_seconds'] == 60.0
    # every prompt is exactly one slice of the largest chunk program
    assert mix['prompt_len']['max'] <= max(model.chunk_sizes(cfg)) == 512
    assert (mix['prompt_len']['max'] + mix['output_len']['max']
            <= cfg['max_cache_len'] == 4608)
    v = cfg['verify']
    assert max(v['prompt_lens']) + v['max_new_tokens'] <= v['pad_to'] \
        <= cfg['max_cache_len']
    # the generator under a seed past 32 bits: ids in the slice held
    reqs = traffic.closed_requests(mix, 2 ** 31 + 40, 127,
                                   model.vocab_size(cfg))
    drawn = [next(reqs) for _ in range(64)]
    assert all(160 <= len(p) <= 512 and 512 <= n <= 4096 for p, n in drawn)
    assert all(2 <= p.min() and p.max() < 16160 for p, _ in drawn)
    # ISSUE 40's arithmetic: E[P] ~ 332, E[O] ~ 2,225, ~1,650 rows a slot
    rng = traffic.rng_for(40, 9)
    out = traffic.draw_lengths(mix['output_len'], rng, 200000).astype(float)
    prompt = traffic.draw_lengths(mix['prompt_len'], rng, 200000)
    assert prompt.mean() == pytest.approx(332, abs=4)
    assert out.mean() == pytest.approx(2225, abs=25)
    held = prompt.mean() + (out ** 2).mean() / (2 * out.mean())
    assert held == pytest.approx(1650, abs=40)
    assert 128 / out.mean() == pytest.approx(0.057, abs=0.002)


def test_the_verify_prompts_are_the_cells_own_sizes():
    """`correct` compares the timed path at the timed sizes: every verify
    prompt is one the traffic could draw (160-512, one 512 slice) but the
    two that reach deep into the cache; none is shorter than the mix's
    shortest, whose rows attend a re-routed earlier position at 1/24-1/72
    (PERF.md 6, PR 40). 48 prompts of 48 tokens: the rows it takes for one
    precision down to come out not correct on most seeds."""
    cfg = _cfg()
    v = cfg['verify']
    mix = harness.load_json(os.path.join(ROOT, 'benchmark', 'traffic',
                                         'reason_closed.json'))
    lens = v['prompt_lens']
    assert len(lens) == 48 <= cfg['max_slots'] and v['max_new_tokens'] == 48
    deep = [n for n in lens if n > mix['prompt_len']['max']]
    assert deep == [1500, 4000]
    assert min(lens) == mix['prompt_len']['min'] == 160
    assert all(mix['prompt_len']['min'] <= n <= mix['prompt_len']['max']
               for n in lens if n not in deep)
    assert 0 < v['margin_eps'] < v['routing_gap_eps']


def test_an_undecided_row_is_held_every_way_and_to_the_served_token():
    """every_way: the plain row, each side, and their combinations with
    the sides' differences added; nearest_way: the way on which the token
    is the best if there is one, else the way it stands nearest the best
    on — so a token no way names is still judged, by the margin rule."""
    plain = np.array([1.0, 0.5, 0.0, -1.0], np.float32)
    a = plain + np.array([-0.8, 0.1, 0.0, 0.0], np.float32)
    b = plain + np.array([0.0, 0.0, 0.9, 0.0], np.float32)
    ways = model.every_way(plain, np.stack([a, b]))
    assert ways.shape == (4, 4)
    np.testing.assert_allclose(ways, [plain, a, b, a + b - plain],
                               atol=1e-6)
    assert [int(w.argmax()) for w in ways] == [0, 1, 0, 2]
    # token 2 is the best only where BOTH ties fall the other way
    np.testing.assert_allclose(model.nearest_way(ways, 2), ways[3])
    np.testing.assert_allclose(model.nearest_way(ways, 0), ways[0])
    # token 3 is the best nowhere: the way kindest to it, a mismatch there
    kind = model.nearest_way(ways, 3)
    np.testing.assert_allclose(kind, ways[1])
    assert int(kind.argmax()) != 3
    assert model.every_way(plain, plain[None][:0]).shape == (1, 4)


def test_near_ties_in_every_combination_far_ones_alone(monkeypatch):
    """reference_logits on a hand-made pass (4 tokens, rows 0-2 decided
    by ties): row 0 has two NEAR ties that name token 2 only together and
    a FAR one that names token 3 alone; row 1 has more near ties than
    _MAX_TIES and row 2's sides did not fit: both undecided (margin 0)."""
    from benchmark.reference import joyai_llm_flash as reference
    cfg = dict(_cfg(), verify=dict(_cfg()['verify'], max_new_tokens=3))
    gap = cfg['verify']['routing_gap_eps']
    plain = np.tile(np.array([1.0, 0.5, 0.0, -1.0], np.float32), (512, 1))
    a = plain[0] + np.array([-0.8, 0.1, 0.0, 0.0], np.float32)
    b = plain[0] + np.array([0.0, 0.0, 0.9, 0.0], np.float32)
    far = plain[0] + np.array([0.0, 0.0, 0.0, 2.5], np.float32)
    crowd = model._MAX_TIES + 1
    alt = {'row': np.array([0, 0, 0] + [1] * crowd),
           'dist': np.array([gap / 2, gap, gap * 1.5] + [gap / 4] * crowd,
                            np.float32),
           'logits': np.stack([a, b, far] + [a] * crowd),
           'overflow': [2]}
    seen = {}

    def logits(weights, ids, either_way=None, **kw):
        seen['either_way'] = either_way
        return plain[:len(ids)], alt

    monkeypatch.setattr(reference, 'logits', logits)

    def served(*tokens):
        ids = np.array([7] + list(tokens), np.int64)
        return model.reference_logits(cfg, {}, ids)

    lg = served(2, 1, 1)        # row 0 scores ids[1]
    assert seen['either_way'][1:] == (model._FAR_TIES * gap,
                                      model._EITHER_WAY_ROWS)
    np.testing.assert_allclose(lg[0], a + b - plain[0], atol=1e-6)
    np.testing.assert_allclose(served(3, 1, 1)[0], far)
    np.testing.assert_allclose(served(0, 1, 1)[0], plain[0])
    for r in (1, 2):            # undecided: the best lowered onto the next
        top = np.sort(lg[r])[-2:]
        assert top[0] == top[1] == 0.5


def test_the_reference_pass_is_as_long_as_the_sequence_not_the_pad():
    """The harness pads every verify sequence to pad_to; the reference
    runs over the 512 / 1,024 / 2,048 ... rows that hold it (never more
    than it was given) and a causal pass gives the same rows either way."""
    ids = np.zeros(4096, np.int64)
    for last, rows in ((207, 512), (511, 512), (512, 1024), (1547, 2048),
                       (4047, 4096)):
        ids[:] = 0
        ids[:last + 1] = 7
        assert model._sequence_rows(ids) == (last, rows)
    assert model._sequence_rows(np.r_[np.full(40, 7), np.zeros(88, int)]) \
        == (39, 128)
    import paddle_tpu as fluid
    cfg = harness.overlay(_cfg(), _cfg()['rehearsal'])
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = model.build_spec(cfg)
        fluid.Executor(fluid.CPUPlace()).run(spec['startup'], scope=scope)
        weights = {n: np.asarray(scope.get(n))
                   for n in scope.local_var_names()
                   if n not in spec['cache_vars']}
    seq = np.random.RandomState(40).randint(2, cfg['vocab_size'], 60)
    short, long = np.zeros(128, np.int64), np.zeros(1500, np.int64)
    short[:60] = long[:60] = seq
    a = model.reference_logits(cfg, weights, short)
    b = model.reference_logits(cfg, weights, long)
    assert a.shape[0] == 128 and b.shape[0] == 512
    assert np.abs(a[:59] - b[:59]).max() <= 1e-4


def test_byte_and_flop_functions_against_hand_counts():
    cfg = _cfg()
    # attention, a layer: q_a 3.146 M + q_b 9.437 M + kv_a 1.180 M +
    # kv_b 4.194 M + o 8.389 M = 26.35 M
    proj = 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
    assert model.latent_proj_params(cfg) == proj == 17956864
    assert model.attention_params(cfg) == proj + 4096 * 2048 == 26345472
    expert = 3 * 2048 * 768
    held = (11 * 26345472 + 3 * 2048 * 7168
            + 10 * (2048 * 256 + 33 * expert) + 2 * 2048 * 16160)
    assert model.weight_params(cfg) == held
    assert held == pytest.approx(1.963e9, rel=1e-3)     # 3.93 GB bfloat16
    # one cached position of one layer: 512 + 64 values in bfloat16
    assert model.kv_row_bytes(cfg) == 1152
    # 128 rows x 8 of 256 experts: 32 * (1 - (31/32)^128) = 31.45 held
    assert model.expected_distinct_experts(cfg, 128) == pytest.approx(
        31.45, abs=0.01)
    assert model.moe_expert_bytes(cfg, 128) == pytest.approx(
        10 * 31.45 * expert * 2, rel=1e-3)              # 2.97 GB
    # every cached row of every live slot once a layer: all heads read it
    rows = 128 * 1650
    assert model.attention_bytes(cfg, rows, 128) == 1152 * 11 * rows
    assert model.attention_bytes(cfg, rows, 128) == pytest.approx(
        2.68e9, rel=5e-3)
    assert model.attention_bytes(cfg, 0, 0) == 0
    # 2 x 32 heads x (576 scored + 512 summed) a cached row a layer
    assert model.attention_flops(cfg, rows) == 2 * 32 * 1088 * 11 * rows
    assert model.attention_flops(cfg, rows) == pytest.approx(162e9, rel=0.01)
    peaks = {'hbm_bytes_per_s': 819e9}
    floor = model.step_floor_seconds(cfg, peaks, rows)
    assert floor == pytest.approx(
        (model.step_dense_bytes(cfg) + model.moe_expert_bytes(cfg, 128)
         + model.attention_bytes(cfg, rows, 128)) / 819e9)
    assert floor == pytest.approx(8.0e-3, rel=0.03)
    assert model.attention_bytes(cfg, rows, 128) / (floor * 819e9) \
        == pytest.approx(0.41, abs=0.015)
    assert model.BOUND == 'memory'


def _timeline(modules, ops=()):
    dev = trace.Device('/device:TPU:0', ops=list(ops), modules=list(modules))
    return trace.Trace(devices=[dev], host=[], window=(0, 100 * MS))


def _run(tr, path=None):
    ctx = types.SimpleNamespace(model=model, cfg=_cfg(),
                                tracer=types.SimpleNamespace(path=path))
    return {'trace': tr, 'ctx': ctx}


def test_prefill_slice_device_ms_on_a_hand_made_timeline():
    """The largest chunk program's dispatches inside the window — the
    one-row program and, where there is one, its row form — by their
    busy time; the smaller chunk and the step are not it."""
    ops = [(10 * MS, 22 * MS, 'a'), (30 * MS, 46 * MS, 'b'),
           (50 * MS, 52 * MS, 'c'), (60 * MS, 74 * MS, 'd'),
           (80 * MS, 81 * MS, 'e')]
    mods = [(10 * MS, 22 * MS, 'jit_prefill_chunk_512(1)'),
            (30 * MS, 46 * MS, 'jit_prefill_chunk_512(1)'),
            (50 * MS, 52 * MS, 'jit_prefill_chunk_128(2)'),
            (60 * MS, 74 * MS, 'jit_prefill_chunk_512x4(3)'),
            (80 * MS, 81 * MS, 'jit_decode_step(4)')]
    assert prefill_slice_device_ms.reduce(_run(_timeline(mods, ops))) \
        == pytest.approx(14.0)
    # a dispatch that crosses the window's edge is not counted
    mods[1] = (95 * MS, 111 * MS, 'jit_prefill_chunk_512(1)')
    assert prefill_slice_device_ms.reduce(_run(_timeline(mods, ops))) \
        == pytest.approx(13.0)


def test_the_new_readers_find_nothing_where_there_is_nothing():
    """An interval without a slice, a trace without a device, a run
    without a trace, a program without the scopes (the parent): None,
    never an error."""
    step_only = _timeline([(1 * MS, 9 * MS, 'jit_decode_step(4)')],
                          [(1 * MS, 9 * MS, 'a')])
    assert prefill_slice_device_ms.reduce(_run(step_only)) is None
    empty = trace.Trace(devices=[], host=[], window=(0, MS))
    for reader in (prefill_slice_device_ms, latent_proj_device_share):
        assert reader.reduce(_run(empty)) is None
        assert reader.reduce(_run(None)) is None
    assert latent_proj_device_share.reduce(_run(step_only)) is None
    other = types.SimpleNamespace(model=types.SimpleNamespace(), cfg={},
                                  tracer=None)
    assert prefill_slice_device_ms.reduce(
        {'trace': step_only, 'ctx': other}) is None


def test_latent_proj_device_share_on_a_hand_made_timeline(monkeypatch):
    """Of a step's operation time, the part whose op_name lies under
    latent_attention/{q_lora, kv_down, q_absorb, v_expand}; the trip
    through the pages (latent_attention/kv_block_attention) is not it."""
    names = {
        'p': 'jit(decode_step)/latent_attention/q_lora/mul/dot_general',
        'q': 'jit(decode_step)/latent_attention/q_absorb/matmul/dot',
        'v': 'jit(decode_step)/latent_attention/v_expand/matmul/dot',
        'k': 'jit(decode_step)/latent_attention/kv_block_attention/while',
        'm': 'jit(decode_step)/moe_topk_ffn/experts/ragged_dot'}
    monkeypatch.setattr(_xplane_meta, 'op_provenance',
                        lambda path: {'/device:TPU:0': names})
    ops = [(0, 1 * MS, 'p'), (1 * MS, 2 * MS, 'q'), (2 * MS, 7 * MS, 'k'),
           (7 * MS, 8 * MS, 'v'), (8 * MS, 10 * MS, 'm')]
    tr = _timeline([(0, 10 * MS, 'jit_decode_step(4)')], ops)
    assert latent_proj_device_share.reduce(_run(tr, path='x')) \
        == pytest.approx(30.0)


def test_the_cell_is_filed_by_name_under_what_it_reports():
    bench = _bench()
    cells = {w['name']: w for w in bench['workloads']}
    assert cells[CELL] == dict(cells[CELL], config='joyai_llm_flash',
                               traffic='reason_closed', chips=1)
    assert len(cells[CELL]['why']) <= 200
    assert len(cells) == 7 and sum(w['chips'] == 4
                                   for w in cells.values()) == 1
    e2e = {m['name'] for m in bench['end_to_end']
           if 'workloads' not in m or CELL in m['workloads']}
    assert e2e == {'itl_p99_ms', 'setup_s'}
    by_name = {m['name']: m for m in bench['per_layer']}
    filed = {n for n, m in by_name.items() if CELL in m.get('workloads', ())}
    assert filed == SHARED | NEW
    for name in NEW:        # this PR's, for this cell alone
        assert by_name[name]['workloads'] == [CELL]
        assert by_name[name]['layer'] == 'Op lowerings / kernels'
        assert by_name[name]['source'] == 'device_trace'
    for name in filed:
        assert by_name[name]['moves'] == (
            'setup_s' if name == 'artifact_load_s' else 'itl_p99_ms')
        assert os.path.exists(os.path.join(
            ROOT, 'benchmark', 'layer_metrics', name + '.py'))
    # the accepted roofline of the paged attention reads this cell too:
    # the latent attention keeps the kv_block_attention scope
    assert by_name['decode_attention_roofline']['workloads'][-1] == CELL
    assert json.dumps(bench).count(CELL) == 1 + 1 + len(filed)


def test_the_cell_runs_end_to_end_under_rehearsal():
    """The harness finds the configuration, the traffic mix and both new
    readers by name and runs the cell at toy sizes on the cpu: a clean
    window, transcripts the expanded reference agrees with, every token
    served through one latent pool a layer."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmark', 'run.py'),
         '--workload', CELL, '--seed', str(2 ** 31 + 40), '--seconds', '4',
         '--trace', '1', '--rehearsal'],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line['rehearsal'] is True and line['correct'] is False
    assert line['rehearsal_checks_passed'] is True, out.stdout[-3000:]
    assert line['failed'] == 0 and line['attempted'] > 0
    metrics = line['metrics']
    assert metrics['compiles_in_window']['value'] == 0
    assert metrics['prefill_slice_device_ms']['value'] > 0
    assert metrics['slices_per_chunk_dispatch']['value'] == 1.0
    assert metrics['step_d2h_bytes']['value'] == 4 * 8      # ids alone
