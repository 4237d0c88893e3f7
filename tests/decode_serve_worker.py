"""Subprocess worker for test_decode_serving.py and decode_serve_smoke.py:
one decode-serving replica "cold start". Loads a continuous-decode
artifact by FILE PATH (the framework must never load into a serving
process), decodes a fixed set of prompts greedily plus one beam request,
and prints the results and the number of XLA backend compiles as a JSON
line:

    python decode_serve_worker.py ARTIFACT_DIR SEED N_PROMPTS MAX_NEW

With AOT sidecars present (export_decode default / cache_ctl prewarm),
compiles must be 0 — the ISSUE 8 warm fresh-process acceptance bar.
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

    with decoding.DecodingPredictor(artifact) as pred:
        vocab = pred._vocab
        big = max(pred._chunks)
        rng = np.random.RandomState(seed)
        prompts = [rng.randint(2, vocab, rng.randint(2, big + 1))
                   for _ in range(n)]
        streams = [pred.submit(p, max_new_tokens=max_new) for p in prompts]
        greedy = [s.result(120) for s in streams]
        beam_ids, beam_scores = pred.generate(prompts[0],
                                              max_new_tokens=max_new,
                                              beam=min(3, pred.max_slots))
        snap = pred.stats.snapshot()
    assert 'paddle_tpu' not in sys.modules, \
        'the framework leaked into the serving process'
    print('DECODE %s' % json.dumps({
        'compiles': compiles[0], 'greedy': greedy,
        'beam_ids': np.asarray(beam_ids).tolist(),
        'beam_scores': np.asarray(beam_scores).tolist(),
        'tokens': snap['tokens'], 'steps': snap['steps']}))
    print('DECODE_OK')


if __name__ == '__main__':
    main()
