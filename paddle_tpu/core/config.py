"""Backend/platform selection + FLAGS-style config registry
(ref: the reference's gflags system, platform/init.cc:81 and python
__bootstrap__ in fluid/__init__.py:97-170).

One rule for the device: `place=None` executors and default meshes run on
jax's default backend (which JAX_PLATFORMS selects from outside);
set_backend() pins another one in-process. Nothing here falls back: a
backend that is named and absent raises where it is resolved.
"""
from __future__ import annotations

import os

_backend_override = None


def set_backend(name):
    """Pin the jax backend ('cpu' | 'tpu' | None for jax's default)."""
    global _backend_override
    _backend_override = name


def get_backend():
    """The backend `place=None` resolves to: the set_backend() pin, else
    jax's default backend."""
    if _backend_override is not None:
        return _backend_override
    import jax
    return jax.default_backend()


def rng_impl():
    """PRNG implementation for the per-step key. On TPU the counter-based
    hardware generator ('rbg') is the default — measured +25% e2e on
    dropout-heavy transformer training vs threefry (PERF_NOTES.md);
    elsewhere (CPU tests) threefry keeps bit-stable fixtures. Override
    with FLAGS_rng_impl / set_flags({'rng_impl': ...})."""
    v = get_flag('rng_impl')
    if v:
        return v
    return 'rbg' if get_backend() == 'tpu' else 'threefry2x32'


def accel_devices():
    import jax
    return jax.devices(get_backend())


# -- FLAGS registry (reference gflags equivalents) ---------------------------
# check_nan_inf -> jax.debug_nans around every Executor step (the moral
#   equivalent of the reference's per-op output scan, operator.cc:896-905).
# deterministic -> when a program has no random_seed, the Executor still
#   derives per-step rng from a fixed root (reproducible across processes);
#   with the flag off it folds in process entropy like the reference's
#   unseeded generators. Deterministic-by-default is the TPU-first choice.
FLAGS = {
    'check_nan_inf': os.environ.get('FLAGS_check_nan_inf', '0') == '1',
    'benchmark': os.environ.get('FLAGS_benchmark', '0') == '1',
    'eager_delete_tensor_gb': float(
        os.environ.get('FLAGS_eager_delete_tensor_gb', '-1')),
    # FLAGS_deterministic is our own flag (deterministic by default); the
    # reference's FLAGS_cudnn_deterministic keeps its narrow meaning and is
    # subsumed (XLA TPU kernels are deterministic), so it is NOT overloaded
    'deterministic': os.environ.get('FLAGS_deterministic', '1') == '1',
    'tensor_array_capacity': int(
        os.environ.get('FLAGS_tensor_array_capacity', '128')),
    # per-step PRNG implementation override (rng_impl() docstring)
    'rng_impl': os.environ.get('FLAGS_rng_impl', '') or None,
    # low-bit dropout keep-decision (0 = off; 8/16 = threshold compare on
    # that many random bits — the PERF_NOTES dropout-tax ablation knob)
    'dropout_bits': int(os.environ.get('FLAGS_dropout_bits', '0')),
}


def get_flag(name, default=None):
    return FLAGS.get(name, default)


def set_flags(d):
    FLAGS.update(d)
