"""The one traffic generator: every mix is a data file under
benchmark/traffic/ that this module turns into requests. Everything here is
a pure function of (traffic file, seed): the same seed gives the same
schedule, lengths and token ids; the program under test only ever sees the
generated requests.

Fields of a serving mix (see benchmark/README.md):
  arrivals    {"process": "poisson"|"gamma", "rate_per_s": r, "cv": c}
              gamma with cv > 1 is burstier than Poisson at the same mean;
              either way a window holds exactly round(r x its length)
              arrivals (see arrival_times)
  prompt_len, output_len
              {"dist": "lognormal", "median", "sigma", "min", "max"} |
              {"dist": "uniform", "min", "max"} | {"dist": "fixed", "value"}
  shared_prefix (optional)
              {"groups": g, "len": <length spec>}: each request starts with
              one of g seeded prefixes (chosen uniformly), then its own
              tokens; absent = every prompt is distinct
"""
from __future__ import annotations

import math
import statistics

import numpy as np

FIRST_TOKEN_ID = 2      # 0 pads, 1 is eos in every configuration here


def rng_for(seed, *stream):
    """An independent generator per (seed, stream...): one for arrivals,
    one per closed-loop client, so adding a client moves no other's draws."""
    return np.random.Generator(np.random.PCG64([int(seed)] +
                                               [int(s) for s in stream]))


_NORMAL = statistics.NormalDist()


def _quantile(spec, u):
    """The length at quantile u of one length spec (before clipping)."""
    dist = spec['dist']
    if dist == 'fixed':
        return float(spec['value'])
    if dist == 'uniform':
        return float(spec['min']) + u * (int(spec['max'])
                                         - int(spec['min']) + 1) - 0.5
    if dist == 'lognormal':
        return math.exp(math.log(float(spec['median']))
                        + float(spec['sigma']) * _NORMAL.inv_cdf(u))
    raise ValueError('unknown length distribution %r' % dist)


def draw_lengths(spec, rng, n, stratified=False):
    """n integer lengths from one length spec. Independent draws by
    default. `stratified`: one draw from each of n equal slices of the
    distribution, in a seeded order — the same distribution, but every
    sample of n holds the same share of short and long lengths, so a tail
    percentile over one window is not moved by how many long prompts the
    seed happened to draw."""
    if stratified:
        u = (rng.permutation(n) + rng.random(n)) / n
    else:
        u = rng.random(n)
    u = np.clip(u, 1e-12, 1 - 1e-12)
    raw = np.array([_quantile(spec, float(x)) for x in u])
    if spec['dist'] == 'fixed':
        return raw.astype(np.int64)
    return np.clip(np.rint(raw), int(spec['min']),
                   int(spec['max'])).astype(np.int64)


def arrival_times(spec, rng, start_s, length_s):
    """Due times in [start_s, start_s + length_s), ascending: exactly
    round(rate x length) of them. 'poisson' is a Poisson process
    conditioned on that count (independent uniform instants: the same
    local burstiness, a fixed amount of work per window); 'gamma' is a
    renewal process with gamma gaps of the given cv (> 1: burstier),
    rescaled to span the interval."""
    n = int(round(float(spec['rate_per_s']) * length_s))
    process = spec.get('process', 'poisson')
    if process == 'poisson':
        t = np.sort(rng.random(n)) * length_s
    elif process == 'gamma':
        cv = float(spec['cv'])
        gaps = rng.gamma(1.0 / (cv * cv), 1.0, n + 1)
        t = np.cumsum(gaps)[:n] / gaps.sum() * length_s
    else:
        raise ValueError('unknown arrival process %r' % process)
    return start_s + t


def _prefixes(traffic, rng, vocab):
    shared = traffic.get('shared_prefix')
    if not shared:
        return []
    return [rng.integers(FIRST_TOKEN_ID, vocab, int(ln)) for ln in
            draw_lengths(shared['len'], rng, int(shared['groups']))]


def _prompts(traffic, rng, n, vocab, prefixes, stratified=False):
    plens = draw_lengths(traffic['prompt_len'], rng, n, stratified)
    olens = draw_lengths(traffic['output_len'], rng, n, stratified)
    out = []
    for plen, olen in zip(plens, olens):
        own = rng.integers(FIRST_TOKEN_ID, vocab, int(plen))
        if prefixes:
            pre = prefixes[int(rng.integers(0, len(prefixes)))]
            own = np.concatenate([pre, own])[:max(int(plen), 1)]
        out.append((own.astype(np.int64), int(olen)))
    return out


def open_schedule(traffic, seed, ramp_s, window_s, vocab):
    """Open loop: [(due_s, prompt ids, max_new_tokens)] for the ramp and
    then the window, due times relative to the run's first instant. Each
    of the two parts holds exactly round(rate x its length) requests whose
    lengths are a stratified sample, so every run of a cell offers the
    same amount of work inside its window."""
    rng = rng_for(seed, 0)
    prefixes = _prefixes(traffic, rng, vocab)
    out = []
    for start, length in ((0.0, ramp_s), (ramp_s, window_s)):
        due = arrival_times(traffic['arrivals'], rng, start, length)
        reqs = _prompts(traffic, rng, len(due), vocab, prefixes,
                        stratified=True)
        out.extend((float(t), p, o) for t, (p, o) in zip(due, reqs))
    return out


def closed_requests(traffic, seed, client, vocab, batch=16):
    """Closed loop: the endless sequence of one client's requests, drawn
    `batch` at a time from the client's own stream."""
    rng = rng_for(seed, 1, client)
    prefixes = _prefixes(traffic, rng_for(seed, 1), vocab)  # all clients'
    while True:
        for req in _prompts(traffic, rng, batch, vocab, prefixes):
            yield req
