"""Subprocess worker: prove the FULL five-axis composition (dp x mp x sp x
ep x pp, every axis simultaneously) in ONE compiled train step, with
per-step loss parity against the single-device run of the same program.

Runs in its own process because --xla_force_host_platform_device_count must
be set before jax initializes, and the main test process is pinned to 8
devices by conftest.py. Invoked by tests/test_mesh_compose.py as

    python mesh_compose_worker.py dp=2 mp=1 sp=2 ep=2 pp=2   (16 devices)
    python mesh_compose_worker.py dp=2 mp=2 sp=2 ep=2 pp=2   (32 devices)

Methodology: reference test_dist_base.py check_with_place (same init, same
data, distributed losses must track single-process losses step for step);
the program is the exact one the driver dryruns (__graft_entry__.
build_five_axis_program).
"""
import os
import re
import sys

AXES = ('dp', 'mp', 'sp', 'ep', 'pp')


def main():
    sizes = {k: 1 for k in AXES}
    for kv in sys.argv[1:]:
        k, v = kv.split('=')
        assert k in AXES, k
        sizes[k] = int(v)
    n = 1
    for v in sizes.values():
        n *= v

    flags = re.sub(r'--xla_force_host_platform_device_count=\d+', '',
                   os.environ.get('XLA_FLAGS', ''))
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=%d' % n).strip()
    os.environ['JAX_PLATFORMS'] = 'cpu'
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)

    import numpy as np
    import jax
    from jax.sharding import Mesh
    import paddle_tpu as fluid
    from paddle_tpu.core.config import set_backend
    set_backend('cpu')
    from paddle_tpu.parallel.compiler import CompiledProgram
    from __graft_entry__ import build_five_axis_program, compose_batch_size

    devs = jax.devices('cpu')
    assert len(devs) >= n, (n, len(devs))

    S = 16
    main_p, startup, loss = build_five_axis_program(
        mp=sizes['mp'], pp=sizes['pp'], seq_len=S)

    scope = fluid.core.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)
    init = {nm: np.asarray(scope.get(nm))
            for nm in scope.local_var_names() if scope.get(nm) is not None}

    # batch must tile the auto microbatch count (2*pp) and the dp axis so
    # the pipeline runs its real GPipe schedule with no fallback pick;
    # enforce the invariant here rather than trusting a silent fallback
    bs = compose_batch_size(sizes['pp'], sizes['dp'])
    m_auto = 2 * sizes['pp']
    assert bs % m_auto == 0 and (bs // m_auto) % sizes['dp'] == 0, \
        (bs, m_auto, sizes)
    rng = np.random.RandomState(0)
    feeds = [{'ids': rng.randint(0, 64, (bs, S)).astype(np.int64),
              'label': rng.randint(0, 8, (bs, 1)).astype(np.int64)}
             for _ in range(3)]

    def run_steps(target):
        sc = fluid.core.Scope()
        for nm, v in init.items():
            sc.set(nm, v)
        ex = fluid.Executor()
        losses = []
        with fluid.scope_guard(sc):
            for f in feeds:
                out, = ex.run(program=target, feed=f, fetch_list=[loss])
                losses.append(float(np.asarray(out).reshape(-1)[0]))
        return losses

    single = run_steps(main_p)
    mesh = Mesh(np.asarray(devs[:n]).reshape(*(sizes[a] for a in AXES)),
                AXES)
    multi = run_steps(CompiledProgram(main_p).with_data_parallel(
        loss_name=loss.name, mesh=mesh))

    assert np.isfinite(single).all(), single
    assert np.isfinite(multi).all(), multi
    assert single[0] != single[-1], "training did not move: %r" % (single,)
    # repo-standard tolerance for single-vs-mesh on CPU fastmath
    # (test_pipeline.py:86); observed divergence is ~1e-7 relative
    np.testing.assert_allclose(single, multi, rtol=2e-3, atol=1e-5)
    # persistent compile cache (ISSUE 5): when the caller points
    # JAX_COMPILATION_CACHE_DIR at a shared dir, report the counters so the
    # test can assert a warm re-run skips the recompile of the largest
    # mesh ever compiled here
    from paddle_tpu.core import compile_cache as cc
    if cc.enabled():
        import json
        s = cc.stats()
        print('CC_STATS %s' % json.dumps(
            {k: s[k] for k in ('exec_hits', 'hlo_hits', 'misses',
                               'compiles', 'xla_compiles_net')}
            | {'compile_s': round(s['compile_s'], 2)}))
    print("MESH_COMPOSE_OK n=%d %s single=%r multi=%r"
          % (n, ' '.join('%s=%d' % (a, sizes[a]) for a in AXES),
             single, multi))


if __name__ == '__main__':
    main()
