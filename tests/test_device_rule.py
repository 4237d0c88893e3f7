"""One rule for the device, and it cannot fall back — what a CPU can know
of it: a place whose backend is absent raises and names what is present,
a serialized executable goes back onto the device it was compiled for, and
chip_smoke.py refuses to pass without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_place_raises_naming_the_platforms_present():
    for place in (fluid.TPUPlace(), fluid.CUDAPlace(0)):
        with pytest.raises(RuntimeError, match=r"needs a 'tpu' device.*"
                                               r"\['cpu'\]"):
            fluid.Executor(place)
    # cpu and the default backend resolve to a concrete local cpu device
    assert fluid.Executor(fluid.CPUPlace())._device.platform == 'cpu'
    assert fluid.Executor()._device.platform == 'cpu'


def test_aot_sidecar_reloads_on_the_device_it_was_compiled_for(tmp_path):
    """jax 0.9.0's deserialize_and_load, given no devices, loads onto ALL
    devices of the default backend ("expected 8 shards, got [1]"): the
    shared loader passes the client and the recorded device instead."""
    import jax
    from paddle_tpu.inference import serve
    dev = jax.devices('cpu')[5]
    spec = jax.ShapeDtypeStruct((4,), np.float32)
    with jax.default_device(dev), serve._fresh_compile('cpu'):
        compiled = jax.jit(lambda x: x * 2 + 1).lower(spec).compile()
    path = str(tmp_path / 'aot_cpu.jaxexec')
    serve._save_aot(path, compiled, 'sha')
    fn = serve._load_aot(path, 'sha')
    out = fn(np.arange(4, dtype=np.float32))
    assert out.devices() == {dev}
    np.testing.assert_array_equal(np.asarray(out), [1, 3, 5, 7])


def test_chip_smoke_exits_nonzero_without_a_tpu():
    """Without an accelerator chip_smoke.py prints no result and names
    the platforms jax did find."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    r = subprocess.run([sys.executable, os.path.join(REPO, 'chip_smoke.py')],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ''
    assert "needs a TPU" in r.stderr and "['cpu']" in r.stderr
