"""Subprocess worker for test_quantize.py and quant_smoke.py: one
QUANTIZED-tier serving replica "cold start". Loads the int8 tier of a
compiled artifact by FILE PATH (the framework must never load into a
serving process), runs one batch from IN.npz, and prints the fetches'
sha256 plus the number of XLA backend compiles as a JSON line:

    python quant_serve_worker.py ARTIFACT_DIR IN.npz [TIER]

With per-tier AOT sidecars present (export_compiled default /
cache_ctl prewarm), compiles must be 0 — the ISSUE 11 warm-replica
acceptance bar, tier by tier.
"""
import hashlib
import json
import os
import sys


def main():
    artifact, in_path = sys.argv[1], sys.argv[2]
    tier = sys.argv[3] if len(sys.argv) > 3 else 'int8'
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
    import serve

    pred = serve.CompiledPredictor(artifact, tier=tier)
    with np.load(in_path) as z:
        feed = {k: z[k] for k in z.files}
    outs = pred.run(feed)
    digest = hashlib.sha256()
    for o in outs:
        digest.update(np.ascontiguousarray(o).tobytes())
    assert 'paddle_tpu' not in sys.modules, \
        'the framework leaked into the serving process'
    print('QUANT %s' % json.dumps({
        'compiles': compiles[0], 'tier': pred.tier,
        'sha': digest.hexdigest(),
        'shapes': [list(np.shape(o)) for o in outs]}))
    print('QUANT_OK')


if __name__ == '__main__':
    main()
