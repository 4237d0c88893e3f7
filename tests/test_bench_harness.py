"""Bench harness contract tests (no real benchmarks run here).

Per-metric isolation (one broken cell never costs another its line), each
cell runs ONCE (no retry: a flaky chip is a finding, not something to
paper over), ANY error line makes the exit code non-zero, the headline is
printed first (insurance) and last (driver parse), and every line names
the device it ran on.

Reference analogue: benchmark/fluid/fluid_benchmark.py:139 prints every
metric it measures.
"""
import json
import sys

import pytest

import bench


def _lines(capsys):
    out = capsys.readouterr().out
    return [json.loads(l) for l in out.splitlines() if l.strip()]


def test_error_is_an_error_line_and_never_retried():
    calls = []

    def broken():
        calls.append(1)
        raise RuntimeError('UNAVAILABLE: Socket closed')

    out = bench.run_metric('m', broken)
    assert len(calls) == 1          # no retry, whatever the message says
    assert out == {'metric': 'm', 'error': 'UNAVAILABLE: Socket closed'}


def test_lines_name_the_device_and_cpu_carries_no_mfu():
    import jax
    cpu = jax.devices('cpu')[0]

    def cell():
        bench._device()             # the executor a real cell builds on
        return bench._line('m', 1.0, 'img/s', 2.0, mfu=None)

    out = bench.run_metric('m', cell)
    assert out['platform'] == 'cpu' and out['device_kind'] == cpu.device_kind
    assert 'mfu' not in out         # None on cpu: the key goes, not a null
    # a host-only cell (no executor) is stamped cpu too; an explicit
    # stamp (the fleet cell's cpu replicas) is kept
    assert bench.run_metric('h', lambda: {'metric': 'h'})['platform'] == 'cpu'
    kept = bench.run_metric('f', lambda: {'metric': 'f', 'platform': 'x',
                                          'device_kind': 'y'})
    assert (kept['platform'], kept['device_kind']) == ('x', 'y')


def test_peak_flops_exact_match_or_error():
    class Dev(object):
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert bench._peak_flops(Dev('tpu', 'TPU v5 lite')) == 197e12
    assert bench._peak_flops(Dev('cpu', 'cpu')) is None
    # 'TPU v5 lite pod' prefix-matched v5e before; 'TPU v5x' got v5p's peak
    for kind in ('TPU v5 lite pod', 'TPU v5x', 'TPU v9'):
        with pytest.raises(KeyError, match='no peak'):
            bench._peak_flops(Dev('tpu', kind))


def test_main_headline_first_and_last(capsys):
    benches = [
        ('headline', lambda: {'metric': 'headline', 'value': 10.0}),
        ('secondary', lambda: {'metric': 'secondary', 'value': 5.0}),
    ]
    rc = bench.main(benches)
    assert rc == 0
    lines = _lines(capsys)
    # headline printed immediately (insurance) AND re-printed last (driver
    # parses the final JSON line as the headline)
    assert lines[0]['metric'] == 'headline'
    assert lines[-1]['metric'] == 'headline'
    assert any(l['metric'] == 'secondary' for l in lines)


def test_main_secondary_fault_nonzero_exit_headline_kept(capsys):
    def dead_secondary():
        raise RuntimeError('INTERNAL: compile failed')

    benches = [
        ('headline', lambda: {'metric': 'headline', 'value': 10.0}),
        ('secondary', dead_secondary),
        ('third', lambda: {'metric': 'third', 'value': 1.0}),
    ]
    rc = bench.main(benches)
    assert rc != 0                  # an error line is a failed run
    lines = _lines(capsys)
    assert any(l['metric'] == 'third' and 'error' not in l for l in lines)
    errs = [l for l in lines if 'error' in l]
    assert errs and errs[0]['metric'] == 'secondary'
    assert lines[-1]['metric'] == 'headline'  # headline survived the fault


def test_main_headline_fault_nonzero_exit(capsys):
    def dead_headline():
        raise ValueError('model build broke')

    benches = [
        ('headline', dead_headline),
        ('secondary', lambda: {'metric': 'secondary', 'value': 5.0}),
    ]
    rc = bench.main(benches)
    assert rc != 0
    lines = _lines(capsys)
    assert 'error' in lines[0] and lines[0]['metric'] == 'headline'
    # the headline's ERROR line is re-printed last: the driver must see an
    # explicit headline failure, never a secondary metric mislabeled as
    # the headline
    assert lines[-1]['metric'] == 'headline' and 'error' in lines[-1]
    assert any(l['metric'] == 'secondary' and 'error' not in l
               for l in lines)


def _fat_line(metric, device_failed=False):
    """A metric line with every field a real bench emits (device-time
    duals included) — the compactness contract must hold for the fattest
    realistic line, not a toy. With device_failed, the device-time miss
    shape (null + capped device_error) rides instead."""
    line = bench._line(metric, 123456.78, 'tokens/s', 33.17,
                       mfu=0.3312, dtype='bf16', batch=4096, seq_len=256,
                       grad_merge_k=2, baseline_ref='flops_eq_xeon',
                       steps_per_dispatch=16,
                       single_step_ms_batch=23.51,
                       speedup_vs_single=9.41)
    if device_failed:
        return bench._attach_device_time(line, lambda: (_ for _ in ()).throw(
            RuntimeError('INTERNAL: Mosaic failed to compile TPU kernel: '
                         'a long backend message that would blow the line '
                         'byte budget if it were not capped ' + 'x' * 200)))
    line.update(device_ms_per_step=2.513, device_k=16,
                device_img_s=5123.45)
    return line


def test_metric_lines_compact_and_under_byte_budget(capsys):
    """Every metric line must parse as STANDALONE JSON under
    LINE_BYTE_BUDGET bytes — the r5 driver artifact's tail byte-cap
    dropped every metric line before the last ~8 because prose baselines
    bloated them (prose belongs in BENCH_NOTES.md now)."""
    benches = [('m%d' % i, lambda i=i: _fat_line('metric_%d_img_s_per_chip'
                                                 % i)) for i in range(3)]
    benches.append(('m3', lambda: _fat_line(
        'metric_3_device_miss_img_s_per_chip', device_failed=True)))
    assert bench.main(benches) == 0
    raw = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    for l in raw:
        parsed = json.loads(l)  # standalone-parsable
        if 'metric' in parsed:
            assert len(l.encode()) <= bench.LINE_BYTE_BUDGET, (len(l), l)
            assert 'note' not in parsed and 'baseline' not in parsed


def test_summary_line_before_headline_reprint(capsys):
    benches = [
        ('headline', lambda: {'metric': 'headline', 'value': 10.0,
                              'vs_baseline': 2.0}),
        ('secondary', lambda: {'metric': 'secondary', 'value': 5.0,
                               'vs_baseline': 1.5}),
        ('broken', lambda: (_ for _ in ()).throw(ValueError('nope'))),
    ]
    assert bench.main(benches) != 0     # 'broken' is an error line
    lines = _lines(capsys)
    # summary is the penultimate line: every metric present, errors marked
    assert lines[-1].get('metric') == 'headline'
    summary = lines[-2].get('summary')
    assert summary == {'headline': [10.0, 2.0], 'secondary': [5.0, 1.5],
                       'broken': 'error'}


def test_device_time_attach_isolated():
    """A device-time measurement failure must not cost the metric it
    rides on — the line keeps its value and records the miss."""
    line = bench._line('m', 1.0, 'img/s', 2.0)

    def boom():
        raise RuntimeError('scan unsupported here')
    out = bench._attach_device_time(dict(line), boom)
    assert out['value'] == 1.0
    assert out['device_ms_per_step'] is None
    assert 'scan unsupported' in out['device_error']

    ok = bench._attach_device_time(dict(line), lambda: (3.21987, 16))
    assert ok['device_ms_per_step'] == 3.22 and ok['device_k'] == 16


def test_device_time_env_disable(monkeypatch):
    monkeypatch.setenv('PTPU_BENCH_DEVICE_TIME', '0')
    line = bench._attach_device_time({'metric': 'm'},
                                     lambda: (_ for _ in ()).throw(
                                         AssertionError('must not run')))
    assert 'device_ms_per_step' not in line


def test_bench_only_typo_runs_nothing(capsys, monkeypatch):
    monkeypatch.setenv('PTPU_BENCH_ONLY', 'berts, resnetx')
    rc = bench.main()
    assert rc != 0
    lines = _lines(capsys)
    # unknown tokens surface as error lines and NO benchmark runs — a typo
    # must not burn TPU time on the full suite
    assert {l['metric'] for l in lines} == {'berts', 'resnetx'}
    assert all('error' in l for l in lines)
