"""Full five-axis composition with dp>1: the flagship distributed claim.

Round-4 state: sp/ep/pp composed in the 8-device dryrun but dp was 1, and
tests covered dp x {mp,sp,ep,pp} pairwise only. These tests compile ONE
train step over dp=2 x sp=2 x ep=2 x pp=2 (16 virtual devices) and over
all five axes >1 (32 virtual devices), asserting per-step loss parity
against the single-device run of the same program — the reference's
multi-device correctness bar (details/multi_devices_graph_pass.cc:393,
test_dist_base.py methodology) applied to the GSPMD design.

Subprocess-based because the device count must be fixed before jax
initializes (conftest pins this process to 8).
"""
import json
import os
import subprocess
import sys

import pytest

WORKER = os.path.join(os.path.dirname(__file__), 'mesh_compose_worker.py')


def _run(spec, timeout=1200, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    p = subprocess.run([sys.executable, WORKER] + spec,
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, "worker failed:\n%s\n%s" % (p.stdout, p.stderr)
    assert 'MESH_COMPOSE_OK' in p.stdout, p.stdout
    cc = [l for l in p.stdout.splitlines() if l.startswith('CC_STATS ')]
    return json.loads(cc[0][len('CC_STATS '):]) if cc else None


def test_16dev_dp2_sp2_ep2_pp2():
    """dp=2 composed with all three novel axes in one compiled step."""
    _run(['dp=2', 'mp=1', 'sp=2', 'ep=2', 'pp=2'])


def test_32dev_all_five_axes():
    """dp=2 x mp=2 x sp=2 x ep=2 x pp=2 — every axis >1 simultaneously."""
    _run(['dp=2', 'mp=2', 'sp=2', 'ep=2', 'pp=2'])


@pytest.mark.slow
def test_64dev_dp4_sp2_ep2_pp4_warm_start(tmp_path):
    """Toward v5p-128 (VERDICT r5: "largest mesh ever compiled is 32 toy
    devices"): dp=4 x sp=2 x ep=2 x pp=4 = 64 virtual devices, run
    TWICE through the persistent compile cache — the cold run records the
    compile time, the warm run (a fresh process, the elastic-restart
    scenario) must hit the executable tier and skip the recompile."""
    spec = ['dp=4', 'mp=1', 'sp=2', 'ep=2', 'pp=4']
    env = {'PTPU_COMPILE_CACHE': '1',
           'JAX_COMPILATION_CACHE_DIR': str(tmp_path / 'cc')}
    cold = _run(spec, timeout=2400, env_extra=env)
    warm = _run(spec, timeout=2400, env_extra=env)
    assert cold is not None and warm is not None
    assert cold['misses'] >= 2          # single-device ref + mesh program
    assert cold['compile_s'] > 0
    assert warm['misses'] == 0, warm    # warm hit must skip recompile
    assert warm['compiles'] == 0, warm
    assert warm['exec_hits'] >= cold['misses'], warm
    # record the 64-device compile time in the test log (PERF_NOTES table)
    print('64dev compose: cold compile_s=%.2f, warm exec_hits=%d'
          % (cold['compile_s'], warm['exec_hits']))
