"""(b) Each traffic generator is a pure function of the seed, and its
lengths match the stated clips and medians."""
import json
import os

import numpy as np
import pytest

from benchmark import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), 'traffic')


def _mix(name):
    with open(os.path.join(TRAFFIC, name + '.json')) as f:
        return json.load(f)


def _same(a, b):
    return (len(a) == len(b) and all(
        x[0] == y[0] and np.array_equal(x[1], y[1]) and x[2] == y[2]
        for x, y in zip(a, b)))


def test_open_schedule_is_a_pure_function_of_the_seed():
    mix = _mix('chat_open')
    a = traffic.open_schedule(mix, 7, 20.0, 40.0, 32000)
    b = traffic.open_schedule(mix, 7, 20.0, 40.0, 32000)
    c = traffic.open_schedule(mix, 8, 20.0, 40.0, 32000)
    assert _same(a, b)
    assert not _same(a, c)
    due = [t for t, _, _ in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 60.0
    rate = mix['arrivals']['rate_per_s']
    # the same amount of work in every run: exactly rate x length requests
    # fall due in the ramp and in the window, whatever the seed
    for sched in (a, c):
        assert sum(t < 20.0 for t, _, _ in sched) == round(20 * rate)
        assert sum(t >= 20.0 for t, _, _ in sched) == round(40 * rate)


def test_window_lengths_are_a_stratified_sample():
    mix = _mix('chat_open')
    n = round(40 * mix['arrivals']['rate_per_s'])
    p95 = []
    for seed in range(6):
        sched = traffic.open_schedule(mix, seed, 20.0, 40.0, 32000)
        lens = sorted(len(p) for t, p, _ in sched if t >= 20.0)
        assert len(lens) == n
        p95.append(lens[int(0.95 * n)])
        assert abs(np.median(lens) - mix['prompt_len']['median']) <= 6
        assert lens[-1] == mix['prompt_len']['max']
    # the tail of the sample barely moves with the seed
    assert max(p95) - min(p95) <= 0.04 * max(p95)


def test_closed_requests_are_a_pure_function_of_seed_and_client():
    mix = _mix('batch_closed')

    def first(seed, client, n=20):
        gen = traffic.closed_requests(mix, seed, client, 32000)
        return [next(gen) for _ in range(n)]
    a, b = first(3, 0), first(3, 0)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    assert any(not np.array_equal(x[0], y[0])
               for x, y in zip(a, first(3, 1)))
    assert any(not np.array_equal(x[0], y[0])
               for x, y in zip(a, first(4, 0)))


@pytest.mark.parametrize('mix_name', ['chat_open', 'batch_closed'])
def test_lengths_match_the_stated_clips_and_medians(mix_name):
    mix = _mix(mix_name)
    rng = traffic.rng_for(11, 0)
    for key in ('prompt_len', 'output_len'):
        spec = mix[key]
        xs = traffic.draw_lengths(spec, rng, 20000)
        assert xs.min() >= spec['min'] and xs.max() <= spec['max']
        # both clips are reached by a lognormal this wide
        assert xs.min() == spec['min'] and xs.max() == spec['max']
        assert abs(np.median(xs) - spec['median']) <= 0.04 * spec['median']


def test_token_ids_avoid_pad_and_eos():
    mix = _mix('chat_open')
    for _, prompt, _ in traffic.open_schedule(mix, 1, 5.0, 15.0, 32000):
        assert prompt.min() >= 2 and prompt.max() < 32000


def test_gamma_arrivals_keep_the_mean_and_add_bursts():
    spec = {'process': 'gamma', 'rate_per_s': 10.0, 'cv': 3.0}
    t = traffic.arrival_times(spec, traffic.rng_for(5, 0), 100.0, 2000.0)
    gaps = np.diff(t)
    assert len(t) == 20000 and t[0] >= 100.0 and t[-1] < 2100.0
    assert 2.5 < gaps.std() / gaps.mean() < 3.5
    poisson = traffic.arrival_times({'rate_per_s': 10.0},
                                    traffic.rng_for(5, 0), 0.0, 2000.0)
    gaps = np.diff(poisson)
    assert 0.95 < gaps.std() / gaps.mean() < 1.05


def test_shared_prefix_groups_share_their_first_tokens():
    mix = dict(_mix('chat_open'))
    mix['prompt_len'] = {'dist': 'fixed', 'value': 96}
    mix['shared_prefix'] = {'groups': 2,
                            'len': {'dist': 'fixed', 'value': 64}}
    reqs = traffic.open_schedule(mix, 2, 10.0, 20.0, 32000)
    heads = {tuple(p[:64]) for _, p, _ in reqs}
    assert len(heads) == 2 and all(len(p) == 96 for _, p, _ in reqs)
