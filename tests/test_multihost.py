"""Multi-host execution: 2 simulated hosts x 4 virtual devices.

Port of the reference's test_dist_base methodology
(python/paddle/fluid/tests/unittests/test_dist_base.py:339 _run_cluster):
spawn trainer subprocesses on 127.0.0.1, each joining the distributed
runtime and feeding its local shard; assert both report IDENTICAL losses
(the SPMD program is one global computation — replicated outputs must
agree bit-for-bit across hosts).
"""
import os
import re
import socket
import subprocess
import sys

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_host_bert_dryrun(tmp_path):
    worker = os.path.join(os.path.dirname(__file__), 'multihost_worker.py')
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop('JAX_PLATFORMS', None)
        env.update({
            'PADDLE_TRAINERS': '2',
            'PADDLE_TRAINER_ID': str(pid),
            'PADDLE_COORDINATOR': '127.0.0.1:%d' % port,
            'XLA_FLAGS': '--xla_force_host_platform_device_count=4',
            'PTPU_MH_CKPT': str(tmp_path / 'mh_ckpt'),
        })
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=560)
        assert p.returncode == 0, \
            "worker failed:\nSTDOUT:%s\nSTDERR:%s" % (out, err[-3000:])
        outs.append(out)

    # Gloo's C++ threads interleave log lines into the same stdout fd, so
    # worker markers are extracted by regex, never by line splitting
    losses = {}
    for out in outs:
        m = re.search(r'\bMHLOSSES (\d+)((?: -?\d+\.\d+)+)', out)
        assert m, "missing loss line: %r" % (out,)
        losses[int(m.group(1))] = [float(v) for v in m.group(2).split()]
    assert set(losses) == {0, 1}, "missing loss lines: %r" % (outs,)
    # one global SPMD computation: replicated loss identical on both hosts
    np.testing.assert_allclose(losses[0], losses[1], rtol=0, atol=0)
    assert all(np.isfinite(losses[0]))
    # training moves the loss
    assert losses[0][0] != losses[0][-1]

    # dist save/load: ONLY process 0 writes; BOTH processes load (the
    # broadcast path) and verify restored state bit-for-bit
    saved = {}
    for out in outs:
        m = re.search(r'\bMHSAVED (\d+) (\d+)\b', out)
        assert m, "missing MHSAVED line: %r" % (out,)
        saved[int(m.group(1))] = int(m.group(2))
    assert saved.get(0, 0) > 0, "process 0 wrote nothing: %r" % (outs,)
    assert saved.get(1) == 0, "process 1 must not write: %r" % (outs,)
    assert all('MHLOADOK' in out for out in outs), \
        "broadcast load failed: %r" % (outs,)
