"""(c) run.py --rehearsal passes end to end for every cell on the cpu and
its last line holds exactly the contract's keys; (d) without a TPU, or on a
device_kind that peaks.json does not list, there is no result."""
import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, 'benchmark', 'run.py')
CONTRACT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}
REHEARSAL_KEYS = {'rehearsal', 'rehearsal_checks_passed'}


def _bench():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def _run(*argv, **env):
    e = dict(os.environ)
    e.pop('XLA_FLAGS', None)      # the suite's 8 virtual devices are not ours
    e.update(env)
    return subprocess.run([sys.executable, RUN] + list(argv), cwd=ROOT,
                          env=e, capture_output=True, text=True,
                          timeout=900)


def _metric_names(bench, kind, cell):
    return {m['name'] for m in bench[kind]
            if 'workloads' not in m or cell in m['workloads']}


@pytest.mark.parametrize('cell', [w['name'] for w in _bench()['workloads']])
@pytest.mark.parametrize('trace', [0, 1])
def test_rehearsal_end_to_end(cell, trace):
    bench = _bench()
    p = _run('--workload', cell, '--seed', '3', '--seconds', '3',
             '--trace', str(trace), '--rehearsal')
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    keys = set(line) - REHEARSAL_KEYS
    assert keys == CONTRACT_KEYS | ({'breakdown'} if trace else set())
    assert line['correct'] is False and line['rehearsal'] is True
    assert line['rehearsal_checks_passed'] is True, p.stdout[-3000:]
    assert line['failed'] == 0 and line['attempted'] > 0
    assert line['device']['platform'] == 'cpu'
    want = _metric_names(bench, 'per_layer' if trace else 'end_to_end', cell)
    got = set(line['metrics'])
    # a reader that finds nothing on the cpu (no device plane) may leave
    # its metric out; nothing may appear that the cell does not declare
    assert got <= want
    if not trace:
        assert got == want
        assert all(m['value'] > 0 for m in line['metrics'].values())
    else:
        assert {'compiles_in_window', 'setup_compiles_net'} <= got
        assert line['metrics']['compiles_in_window']['value'] == 0
        assert set(line['device']) >= {'busy_s', 'window_s'}
        assert set(line['breakdown']) == {'device_ops', 'idle_gaps'}
    units = {m['name']: m['unit']
             for m in bench['end_to_end'] + bench['per_layer']}
    assert all(m['unit'] == units[n] for n, m in line['metrics'].items())


def test_without_a_tpu_there_is_no_result():
    p = _run('--workload', 'resnet50.train_1chip', '--seconds', '1',
             JAX_PLATFORMS='cpu')
    assert p.returncode != 0
    assert '{' not in p.stdout
    assert 'needs a tpu' in p.stderr


def test_unknown_workload_is_refused():
    p = _run('--workload', 'nope', JAX_PLATFORMS='cpu')
    assert p.returncode != 0 and '{' not in p.stdout


def _fake_devices(kind, n):
    return [types.SimpleNamespace(platform='tpu', device_kind=kind, id=i)
            for i in range(n)]


@pytest.mark.parametrize('kind,n,cell,why', [
    ('TPU v9 imaginary', 1, 'resnet50.train_1chip', 'no peaks on record'),
    ('TPU v5 lite', 1, 'resnet50.train_dp4', 'needs 4 chip'),
])
def test_unknown_kind_or_too_few_chips_is_refused(monkeypatch, capsys, kind,
                                                  n, cell, why):
    import jax
    sys.path.insert(0, ROOT)
    from benchmark import run as run_mod
    monkeypatch.setattr(jax, 'devices', lambda *a: _fake_devices(kind, n))
    args = run_mod.parse_args(['--workload', cell])
    assert run_mod.prepare(args) == 2
    assert why in capsys.readouterr().err


def test_benchmark_json_names_files_that_exist():
    bench = _bench()
    for c in bench['configs']:
        assert os.path.isfile(os.path.join(ROOT, c['file']))
    for w in bench['workloads']:
        assert os.path.isfile(os.path.join(
            ROOT, 'benchmark', 'traffic', w['traffic'] + '.json'))
    for kind, package in (('end_to_end', 'end_to_end'),
                          ('per_layer', 'layer_metrics')):
        for m in bench[kind]:
            assert os.path.isfile(os.path.join(
                ROOT, 'benchmark', package, m['name'] + '.py')), m['name']
    e2e = {m['name']: m for m in bench['end_to_end']}
    for m in bench['per_layer']:
        cells = m.get('workloads') or [w['name'] for w in bench['workloads']]
        moved = e2e[m['moves']]
        assert set(cells) <= set(moved.get('workloads') or cells), m['name']
