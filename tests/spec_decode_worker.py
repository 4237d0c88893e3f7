"""Subprocess worker for test_spec_decode.py and spec_decode_smoke.py:
one SPECULATIVE decode-serving replica "cold start". Loads a
continuous-decode artifact that carries a verify program by FILE PATH
(the framework must never load into a serving process), attaches the
n-gram drafter, decodes a fixed set of self-repetitive prompts, and
prints transcripts, speculative stats, and the number of XLA backend
compiles as a JSON line:

    python spec_decode_worker.py ARTIFACT_DIR SEED N_PROMPTS MAX_NEW

With AOT sidecars present (export_decode default / cache_ctl prewarm
covering the decode_verify/ program), compiles must be 0 — the ISSUE 17
warm fresh-process acceptance bar.
"""
import json
import os
import sys


def main():
    artifact, seed, n, max_new = (sys.argv[1], int(sys.argv[2]),
                                  int(sys.argv[3]), int(sys.argv[4]))
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    import numpy as np
    from jax import monitoring

    compiles = [0]

    def _listener(event, secs, **kw):
        if event == '/jax/core/compile/backend_compile_duration':
            compiles[0] += 1

    monitoring.register_event_duration_secs_listener(_listener)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(os.path.dirname(here), 'paddle_tpu',
                                    'inference'))
    import decoding

    with decoding.DecodingPredictor(artifact, draft='ngram') as pred:
        vocab = pred._vocab
        big = max(pred._chunks)
        rng = np.random.RandomState(seed)
        # self-repetitive prompts so the n-gram drafter actually fires
        # (verify dispatches happen regardless of acceptance)
        prompts = []
        for _ in range(n):
            pat = rng.randint(2, vocab, 2)
            plen = int(rng.randint(4, big + 1))
            prompts.append(np.tile(pat, plen)[:plen])
        streams = [pred.submit(p, max_new_tokens=max_new) for p in prompts]
        out = [s.result(120) for s in streams]
        snap = pred.stats.snapshot()
    assert 'paddle_tpu' not in sys.modules, \
        'the framework leaked into the serving process'
    print('SPEC %s' % json.dumps({
        'compiles': compiles[0], 'greedy': out,
        'verify_steps': snap['verify_steps'], 'drafted': snap['drafted'],
        'accepted': snap['accepted'], 'acc_rate': snap['acc_rate'],
        'tokens_per_dispatch': snap['tokens_per_dispatch'],
        'tokens': snap['tokens']}))
    print('SPEC_OK')


if __name__ == '__main__':
    main()
