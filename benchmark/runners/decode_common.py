"""What the two decode runners share: the cached artifact, the predictor,
consumer-side timing, the counters and the correctness check.

The benchmark times on the CONSUMER side: a token's time is the instant a
consumer thread receives it from its TokenStream, never a time the program
recorded itself.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import time

import numpy as np

from .. import harness
from ..harness import say
from ..traffic import rng_for

_COUNTERS = ('steps', 'chunk_slices', 'prefills', 'tokens', 'requests',
             'shed', 'expired')


def get_artifact(ctx):
    """The exported decode artifact for this configuration and these
    program sources: served from the cache root when it is there, else
    built, exported (with AOT sidecars) and kept. The weights are saved
    beside it once, because the artifact bakes them and the reference
    needs them. Returns (artifact dir, weights path, facts of the export
    or None when it was cached)."""
    root = harness.artifact_dir(ctx)
    art = os.path.join(root, 'decode_art')
    weights = os.path.join(root, 'weights.npz')
    if _whole(root):
        return art, weights, None
    import paddle_tpu as fluid
    from paddle_tpu.inference import export_decode
    shutil.rmtree(root, ignore_errors=True)
    partial = root + '.partial'
    shutil.rmtree(partial, ignore_errors=True)
    os.makedirs(partial)
    scope = fluid.core.Scope()
    t0 = time.perf_counter()
    with fluid.scope_guard(scope), fluid.unique_name.guard():
        spec = ctx.model.build_spec(ctx.cfg)
        fluid.Executor().run(spec['startup'], scope=scope)
        caches = set(spec['cache_vars'])
        np.savez(os.path.join(partial, 'weights.npz'),
                 **{n: np.asarray(scope.get(n))
                    for n in scope.local_var_names()
                    if n not in caches and hasattr(scope.get(n), 'shape')})
        t1 = time.perf_counter()
        export_decode(spec, os.path.join(partial, 'decode_art'), scope=scope)
        export_s = time.perf_counter() - t1
    del scope, spec
    gc.collect()
    facts = {'build_s': t1 - t0, 'export_s': export_s,
             'module_bytes': 0, 'sidecar_bytes': 0}
    for base, _, names in os.walk(partial):
        for n in names:
            size = os.path.getsize(os.path.join(base, n))
            if n.endswith('.jaxexport'):
                facts['module_bytes'] += size
            elif n.endswith('.jaxexec'):
                facts['sidecar_bytes'] += size
    facts['files'] = _listing(partial)
    with open(os.path.join(partial, 'export.json'), 'w') as f:
        json.dump(facts, f)
    os.rename(partial, root)
    say('decode artifact exported (cold run only)',
        **{k: v for k, v in facts.items() if k != 'files'})
    return art, weights, facts


def _listing(root):
    """{relative path: size} of every file under root but export.json."""
    out = {}
    for base, _, names in os.walk(root):
        for n in names:
            p = os.path.join(base, n)
            rel = os.path.relpath(p, root)
            if rel != 'export.json':
                out[rel] = os.path.getsize(p)
    return out


def _whole(root):
    """A cached artifact counts only if every file its export listed is
    still there at its size: a cache directory that something pruned by
    size keeps the small files and drops the executables."""
    try:
        with open(os.path.join(root, 'export.json')) as f:
            files = json.load(f)['files']
    except (OSError, ValueError, KeyError):
        return False
    return _listing(root) == files


class Served(object):
    """The predictor under test plus the consumer-side record of what it
    delivered."""

    def __init__(self, ctx):
        from paddle_tpu.inference import DecodingPredictor
        self.ctx = ctx
        self.art, self.weights_path, self.export_facts = get_artifact(ctx)
        self.vocab = ctx.model.vocab_size(ctx.cfg)
        with ctx.spans.span('artifact_load'):
            self.pred = DecodingPredictor(self.art)
            self._warm()

    def _warm(self):
        """One request through the public door that touches every program
        the traffic uses: a prompt of (largest chunk + a few) tokens takes
        one slice of the largest chunk program and one of the smallest,
        then decode steps. DecodingPredictor.warmup() is not used: it
        re-zeroes the whole KV pool through an undonated copy while the
        old pool is alive, which at 128 slots x 2048 positions does not
        fit the chip beside it (PERF.md, Findings PR 22)."""
        chunks = self.ctx.model.chunk_sizes(self.ctx.cfg)
        n = chunks[-1] + max(chunks[0] // 2, 1)
        prompt = np.arange(n, dtype=np.int64) % (self.vocab - 2) + 2
        self.pred.generate(prompt, max_new_tokens=3, timeout=900)
        self.pred.block_manager.evict_all_prefixes()
        self.pred.stats.reset()

    def counters(self):
        snap = self.pred.stats.snapshot()
        out = {k: snap.get(k, 0) for k in _COUNTERS}
        out['busy_s'] = float(self.pred.stats.busy_s)
        out['active_slot_steps'] = int(self.pred.stats.active_slot_steps)
        out['slot_steps'] = int(self.pred.stats.slot_steps)
        out['blocks_in_use'] = snap.get('blocks_in_use', 0)
        return out

    def consume(self, stream, times):
        """Iterate one stream to its end on the calling thread, appending
        the perf_counter instant of each delivery to `times` as it
        arrives. Returns the stream's error, or None."""
        try:
            for _ in stream:
                times.append(time.perf_counter())
        except Exception as e:      # the stream's own failure: recorded
            return e
        return None

    def close(self):
        self.pred.close()


def delta(a, b):
    return {k: b[k] - a[k] for k in a if k != 'blocks_in_use'}


def sample_traced(served, tracer, seconds):
    """Hold the main thread inside the traced window for `seconds`;
    returns (counters before, counters after, (start, end) of the
    interval on perf_counter)."""
    tracer.start()
    c0 = served.counters()
    t0 = time.perf_counter()
    time.sleep(seconds)
    t1 = time.perf_counter()
    c1 = served.counters()
    tracer.stop()
    return c0, c1, (t0, t1)


def cached_rows(records, lo, hi, samples=8):
    """Mean over `samples` instants in [lo, hi] of the K/V positions the
    decoding requests hold: prompt + tokens delivered so far, for every
    request between its first token and its end. This is what a decode
    step NEEDS to read; blocks the prefix cache keeps after a request has
    ended are not in it."""
    totals = []
    for k in range(samples):
        t = lo + (hi - lo) * (k + 0.5) / samples
        rows = 0
        for r in records:
            times = r['times']
            if times and times[0] <= t and (r['done'] is None
                                            or r['done'] > t):
                rows += r['plen'] + sum(1 for x in times if x <= t)
        totals.append(rows)
    return float(np.mean(totals))


def itl_gaps_ms(streams, lo, hi):
    """Gaps between consecutive deliveries of one stream, for the gaps
    that END inside [lo, hi)."""
    out = []
    for times in streams:
        for a, b in zip(times, times[1:]):
            if lo <= b < hi:
                out.append((b - a) * 1e3)
    return out


def tokens_in(streams, lo, hi):
    return sum(1 for times in streams for t in times if lo <= t < hi)


def verify_transcripts(served):
    """Serve the configuration's seeded verify prompts greedily on the
    idle predictor (all at once: continuous batching is the path under
    test) and hold every served token to the full-forward argmax of the
    configuration's plain reference (its module's reference_logits) wherever the reference's top-two margin exceeds
    margin_eps — prefill-then-decode through the block cache against one
    teacher-forced reference pass over prompt + served tokens. Tokens
    under the margin are skipped and counted; more than half skipped
    fails the check."""
    ctx = served.ctx
    v = ctx.cfg['verify']
    rng = rng_for(ctx.seed, 2)     # a stream of its own
    weights = dict(np.load(served.weights_path))
    eps = float(v['margin_eps'])
    pad_to = int(v['pad_to'])
    prompts = [rng.integers(2, served.vocab, int(n)).astype(np.int64)
               for n in v['prompt_lens']]
    streams = [served.pred.submit(
        p, max_new_tokens=int(v['max_new_tokens'])) for p in prompts]
    compared = skipped = wrong = wrong_under = 0
    worst = 0.0
    for prompt, stream in zip(prompts, streams):
        toks = list(stream.result(900))
        seq = np.concatenate([prompt, np.asarray(toks, np.int64)])
        if len(seq) > pad_to:
            raise ValueError('verify sequence of %d exceeds pad_to %d'
                             % (len(seq), pad_to))
        padded = np.zeros(pad_to, np.int64)
        padded[:len(seq)] = seq       # causal: the pad cannot reach back
        lg = np.asarray(ctx.model.reference_logits(ctx.cfg, weights, padded))
        for j, tok in enumerate(toks):
            row = lg[len(prompt) - 1 + j]
            top2 = np.partition(row, -2)[-2:]
            margin = float(top2[1] - top2[0])
            match = int(np.argmax(row)) == int(tok)
            if not match:
                worst = max(worst, margin)
            if margin <= eps:
                skipped += 1
                wrong_under += not match
                continue
            compared += 1
            wrong += not match
    total = compared + skipped
    ok = wrong == 0 and total > 0 and skipped * 2 <= total
    say('decode verify', compared=compared, skipped_under_margin=skipped,
        wrong=wrong, mismatched_under_margin=wrong_under,
        largest_mismatch_margin=worst, eps=eps, ok=ok)
    return ok


def verify(served, result, thread_errors=()):
    """`correct` for a decode cell: a clean window (no compile, shed,
    expiry or failed request, no exception on a thread of the load
    generator) and transcripts that agree with the reference."""
    c = result['counters_window']
    for name, err in thread_errors:
        say('decode verify: load generator thread failed', thread=name,
            error=repr(err))
    clean = (not result['compiles_in_window'] and not c['shed']
             and not c['expired'] and not result['failed']
             and not thread_errors)
    if not clean:
        say('decode verify: window not clean',
            compiles=result['compiles_in_window'], shed=c['shed'],
            expired=c['expired'], failed=result['failed'],
            thread_errors=len(thread_errors))
    return verify_transcripts(served) and clean
