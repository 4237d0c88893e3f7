from . import registry  # noqa: F401
from .scope import Scope, global_scope, scope_guard  # noqa: F401
from .lod import LoDArray, create_lod_array  # noqa: F401


class EOFException(Exception):
    """Raised by pipeline readers at end of epoch (ref: fluid.core.EOFException)."""
    pass


def to_dlpack(value):
    """DLPack export (ref framework/dlpack_tensor.cc) — jax arrays speak
    the protocol natively via __dlpack__ (zero-copy)."""
    from .lod import unwrap
    return unwrap(value).__dlpack__()


def from_dlpack(capsule_or_array):
    """Import a DLPack capsule / any __dlpack__ provider as a device
    array."""
    import jax.numpy as jnp
    return jnp.from_dlpack(capsule_or_array)
