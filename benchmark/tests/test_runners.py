"""The runners know no model and lose no request: (e) a second train
configuration runs through train_loop untouched, its feed and reference
coming from its own module; (f) the open loop is held to its schedule — a
request that fell due and was never submitted is attempted, failed and
counted at the worst value, and a dead load-generator thread makes the run
not correct."""
import importlib
import time
import types

import pytest

from benchmark import harness
from benchmark.runners import decode_common, decode_open, train_loop
from benchmark.tests import second_config

PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


def _ctx(cfg, traffic, seconds, model=None, chips=1):
    cell = {'name': 'test.cell', 'chips': chips}
    ctx = harness.Context({}, cell, cfg, None, traffic, 3, seconds, 0, True,
                          model=model)
    ctx.peaks = PEAKS
    ctx.tracer = harness.Tracer(False, None, ctx.spans)
    return ctx


def test_a_second_config_runs_through_train_loop_untouched():
    ctx = _ctx(second_config.CFG, second_config.TRAFFIC, 0.5,
               model=second_config)
    runner = train_loop.Runner(ctx)
    runner.setup()
    result = runner.window(0.5)
    assert runner.verify() is True
    assert result['attempted'] > 0 and result['failed'] == 0
    assert result['losses_finite'] and result['compiles_in_window'] == 0
    assert result['last_loss'] < result['first_loss']
    run = {'ctx': ctx, 'runner': runner, 'result': result, 'trace': None,
           'setup': {}}
    for package, metric in (('end_to_end', 'train_samples_per_s'),
                            ('layer_metrics', 'train_mfu'),
                            ('layer_metrics', 'exe_call_ms_p50')):
        reader = importlib.import_module('benchmark.%s.%s'
                                         % (package, metric))
        assert reader.reduce(run) > 0
    # the comparison is the configuration's: a tolerance it cannot meet
    # fails the check
    tight = harness.overlay(second_config.CFG, {'verify': {'compare': {
        'pred': {'tol': 0.0}}}})
    ctx.cfg = tight
    assert runner.verify() is False


class _Stream(object):
    def __init__(self, n):
        self.n, self.cancelled = n, False

    def __iter__(self):
        for i in range(self.n):
            if self.cancelled:
                return
            time.sleep(0.002)
            yield i

    def cancel(self):
        self.cancelled = True


class _Served(object):
    """Stands where decode_common.Served does; its submit() raises from
    the `die_after`-th call on."""

    def __init__(self, die_after=None):
        self.vocab, self.calls, self.die_after = 64, 0, die_after
        self.pred = types.SimpleNamespace(max_slots=4, submit=self.submit)

    def submit(self, prompt, max_new_tokens):
        self.calls += 1
        if self.die_after is not None and self.calls > self.die_after:
            raise RuntimeError('submit failed')
        return _Stream(max_new_tokens)

    def counters(self):
        return dict.fromkeys(
            decode_common._COUNTERS + ('busy_s', 'active_slot_steps',
                                       'slot_steps', 'blocks_in_use'), 0)

    consume = decode_common.Served.consume

    def close(self):
        pass


OPEN = {'runner': 'decode_open',
        'arrivals': {'process': 'poisson', 'rate_per_s': 40.0},
        'prompt_len': {'dist': 'fixed', 'value': 4},
        'output_len': {'dist': 'fixed', 'value': 3},
        'ramp_seconds': 0.25, 'drain_seconds': 0.5, 'consumers': 4}


@pytest.mark.parametrize('die_after,failed', [(None, 0), (20, 30)])
def test_open_loop_is_held_to_its_schedule(monkeypatch, die_after, failed):
    monkeypatch.setattr(decode_common, 'verify_transcripts', lambda s: True)
    ctx = _ctx({'name': 'none', 'model': 'transformer_base_lm'}, OPEN, 1.0)
    runner = decode_open.Runner(ctx)
    runner.setup(_Served(die_after))
    r = runner.window(1.0)
    # 10 requests fall due in the ramp and 40 in the window, whatever got
    # submitted: the 30 the dead generator never sent are failed and worst
    assert r['attempted'] == 40 and r['failed'] == failed
    assert len(r['ttft_ms']) == 40 and len(r['generator_lag_ms']) == 40
    assert sorted(r['ttft_ms'])[-failed:] == [1500.0] * failed or not failed
    assert len(runner.thread_errors) == (1 if failed else 0)
    assert runner.verify() is (not failed)
    runner.close()
