#!/usr/bin/env python3
"""Records the small TPU trace that test_trace.py reads
(benchmark/tests/fixture_v5e.xplane.pb). Run on the chip:

    python3 benchmark/tests/record_fixture.py <out dir>

Three dispatches of one jitted program ('jit_fixture_step': a chain of
1024x1024 bf16 matmuls), each inside a 'bench/exe_run' span and followed by
a 'bench/sync' span and a 5 ms host sleep, all inside 'bench/traced_window'.
It prints what the reduction reads from the trace, so that the numbers in
test_trace.py can be checked against the dump by hand.
"""
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out):
    import jax
    import jax.numpy as jnp
    from jax import profiler
    from benchmark import trace as trace_mod

    @jax.jit
    def fixture_step(x):
        for _ in range(24):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    fixture_step(x).block_until_ready()
    tdir = os.path.join(out, 'trace')
    shutil.rmtree(tdir, ignore_errors=True)
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    profiler.start_trace(tdir, profiler_options=opts)
    with profiler.TraceAnnotation('bench/traced_window'):
        for _ in range(3):
            with profiler.TraceAnnotation('bench/exe_run'):
                y = fixture_step(x)
            with profiler.TraceAnnotation('bench/sync'):
                y.block_until_ready()
            time.sleep(0.005)
    profiler.stop_trace()
    path = trace_mod.find_xplane(tdir)
    dst = os.path.join(out, 'fixture_v5e.xplane.pb')
    shutil.copy(path, dst)
    t = trace_mod.load(dst)
    lo, hi = t.window
    d = t.devices[0]
    print('bytes', os.path.getsize(dst))
    print('window_s', trace_mod.window_seconds(t))
    print('busy_s', trace_mod.mean_busy_seconds(t))
    print('programs', {k: v for k, v in
                       trace_mod.program_times(d, lo, hi).items()})
    print('modules', [(s - lo, e - lo, n) for s, e, n in d.modules])
    print('gaps', [(s - lo, e - lo) for s, e in
                   trace_mod.idle_gaps(d, lo, hi) if e - s > 100000])
    print('idle_by_host', trace_mod.idle_by_host_activity(t))
    print('top_ops', trace_mod.top_ops(t, 5))
    print('n_ops', len(d.ops))


if __name__ == '__main__':
    main(sys.argv[1] if len(sys.argv) > 1 else 'chiprun_out/fixture')
