"""Test config: force an 8-device virtual CPU platform so SPMD/mesh tests
exercise real sharding without TPU hardware (the driver's dryrun_multichip
uses the same mechanism)."""
import os

os.environ.setdefault('XLA_FLAGS',
                      (os.environ.get('XLA_FLAGS', '') +
                       ' --xla_force_host_platform_device_count=8').strip())
os.environ['JAX_PLATFORMS'] = 'cpu'

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test builds into fresh default programs + scope."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    main, startup = fluid.Program(), fluid.Program()
    prev_m = fluid.switch_main_program(main)
    prev_s = fluid.switch_startup_program(startup)
    scope = fluid.core.Scope()
    with fluid.scope_guard(scope), unique_name.guard():
        yield
    fluid.switch_main_program(prev_m)
    fluid.switch_startup_program(prev_s)
