"""Graph-program front-end: Program / Block / Operator / Variable.

TPU-native re-design of the reference's ProgramDesc stack
(ref: paddle/fluid/framework/framework.proto:184, python/paddle/fluid/framework.py:232,546,992,1510).
The reference serializes the graph to protobuf and interprets it op-by-op in
C++; here the Program IS the IR — the Executor traces it once into a pure JAX
function and XLA compiles it. Ops therefore carry only: type, input/output
var names per slot, and attrs. Shape/dtype inference runs at op-append time
(mirroring the reference's compile-time InferShape pass).
"""
from __future__ import annotations

import contextlib
import copy
import numpy as np

from . import unique_name

# ---------------------------------------------------------------------------
# dtype handling. The reference uses proto VarType enums; we use numpy dtypes
# canonicalized to strings ('float32', 'int64', ...). bfloat16 is first-class
# (TPU native).
# ---------------------------------------------------------------------------
_DTYPE_ALIASES = {
    'float': 'float32', 'double': 'float64', 'half': 'float16',
    'int': 'int32', 'long': 'int64', 'bool_': 'bool',
    'fp32': 'float32', 'fp64': 'float64', 'fp16': 'float16',
    'bf16': 'bfloat16',
}


# reference proto VarType.Type enum values (framework.proto:106) — dtype
# attrs in reference-saved programs arrive as these ints
_PROTO_DTYPE = {0: 'bool', 1: 'int16', 2: 'int32', 3: 'int64',
                4: 'float16', 5: 'float32', 6: 'float64',
                20: 'uint8', 21: 'int8'}
PROTO_DTYPE_ENUM = {v: k for k, v in _PROTO_DTYPE.items()}


def convert_dtype(dtype):
    """Canonicalize a dtype spec (str / np.dtype / jnp dtype / reference
    VarType enum int) to a string."""
    if dtype is None:
        return None
    if isinstance(dtype, int) and not isinstance(dtype, bool):
        if dtype in _PROTO_DTYPE:
            return _PROTO_DTYPE[dtype]
        raise TypeError("unknown dtype enum %r" % (dtype,))
    if isinstance(dtype, str):
        s = _DTYPE_ALIASES.get(dtype, dtype)
    else:
        try:
            s = np.dtype(dtype).name
        except TypeError:
            s = str(dtype)
    if s == 'bfloat16':
        return 'bfloat16'
    # validate through numpy for everything else
    if s not in ('float32', 'float64', 'float16', 'int8', 'uint8', 'int16',
                 'int32', 'int64', 'bool'):
        s = np.dtype(s).name
    return s


def is_float_dtype(dtype):
    return convert_dtype(dtype) in ('float16', 'bfloat16', 'float32', 'float64')


def int_t():
    """Runtime carrier dtype for declared-int64 outputs (int32 without
    jax x64; resolved per call so an x64 toggle after import is honored)."""
    return runtime_dtype('int64')


def runtime_dtype(dtype):
    """The dtype a declared var dtype actually carries on device: jax
    without x64 stores int64/float64 as 32-bit. Canonicalizing HERE keeps
    declared dtypes ('int64' per reference op protos) separate from carrier
    dtypes, instead of warning on every truncating astype."""
    import jax
    if dtype is None:
        return None
    s = convert_dtype(dtype)
    if s == 'bfloat16':
        import jax.numpy as jnp
        return jnp.bfloat16
    return jax.dtypes.canonicalize_dtype(np.dtype(s))


class Variable(object):
    """A named tensor slot in a Block (ref: fluid/framework.py:232).

    shape may contain -1 (batch/dynamic dim resolved at feed time).
    lod_level > 0 marks variable-length sequence semantics (ref LoDTensor,
    paddle/fluid/framework/lod_tensor.h:110) — carried as metadata; the
    runtime representation is (dense data, row-split offsets).
    """

    def __init__(self, block, name, shape=None, dtype='float32', lod_level=0,
                 persistable=False, stop_gradient=False, trainable=None,
                 type='lod_tensor', initializer=None, is_data=False,
                 need_check_feed=False):
        self.block = block
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = convert_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.type = type  # 'lod_tensor' | 'selected_rows' | 'tensor_array' | 'reader' | 'raw'
        self.initializer = initializer
        self.is_data = is_data
        self.is_parameter = False
        # optional GSPMD partition spec (tuple of mesh axis names / None per
        # dim) — set via paddle_tpu.parallel.shard_parameter for TP/EP
        self.sharding_spec = None

    # -- python operator sugar (ref: layers/math_op_patch.py) is installed by
    #    paddle_tpu.layers.math_op_patch at import time.

    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def astype(self, dtype):
        from .layers import tensor as _t
        return _t.cast(self, dtype)

    def __repr__(self):
        return ("Variable(name=%r, shape=%r, dtype=%s, lod_level=%d%s)" %
                (self.name, self.shape, self.dtype, self.lod_level,
                 ', persistable' if self.persistable else ''))

    __str__ = __repr__


class Parameter(Variable):
    """Trainable persistable variable (ref: fluid/framework.py:2104)."""

    def __init__(self, block, name, shape, dtype, trainable=True,
                 optimize_attr=None, regularizer=None, gradient_clip_attr=None,
                 do_model_average=False, **kw):
        super().__init__(block, name, shape=shape, dtype=dtype,
                         persistable=True, stop_gradient=not trainable, **kw)
        self.trainable = trainable
        self.optimize_attr = optimize_attr or {'learning_rate': 1.0}
        self.regularizer = regularizer
        self.gradient_clip_attr = gradient_clip_attr
        self.do_model_average = do_model_average
        self.is_parameter = True


class Operator(object):
    """One op in a block (ref: fluid/framework.py:546).

    inputs/outputs: dict slot_name -> list[str] of var names.
    attrs: plain-python attributes (must be hashable/serializable).
    Sub-block attrs (control flow) store the block index under attrs['sub_block'].
    """

    _uid_counter = [0]

    @staticmethod
    def _norm_slot(v):
        if v is None:
            return []
        if isinstance(v, (Variable, str)):
            v = [v]
        out = []
        for x in v:
            if isinstance(x, Variable):
                out.append(x.name)
            elif isinstance(x, str):
                out.append(x)
            else:
                raise TypeError(
                    "op inputs/outputs must be Variables or names, got %r "
                    "(wrap constants with layers.assign first)"
                    % (type(x).__name__,))
        return out

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs = {k: self._norm_slot(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: self._norm_slot(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})
        # stable per-op uid: seeds op-local RNG streams (dropout etc.) so the
        # vjp-derived grad lowering reproduces the forward's randomness.
        # Counted PER PROGRAM: identical model code builds identical uid
        # streams regardless of what was built before in the process, so
        # same-seed programs are reproducible by construction.
        if '_op_uid' not in self.attrs:
            program = block.program
            program._op_uid_counter += 1
            self.attrs['_op_uid'] = program._op_uid_counter
        if _name_scopes and 'op_namescope' not in self.attrs:
            self.attrs['op_namescope'] = '/'.join(_name_scopes)

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    def input_arg_names(self):
        return [n for v in self.inputs.values() for n in v]

    def output_arg_names(self):
        return [n for v in self.outputs.values() for n in v]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def has_attr(self, name):
        return name in self.attrs

    def __repr__(self):
        ins = {k: v for k, v in self.inputs.items() if v}
        outs = {k: v for k, v in self.outputs.items() if v}
        return "{%s: %s -> %s}" % (self.type, ins, outs)


class Block(object):
    """A straight-line list of ops + a var scope (ref: fluid/framework.py:992)."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}  # name -> Variable
        self.ops = []   # list[Operator]

    @property
    def parent_block(self):
        return self.program.block(self.parent_idx) if self.parent_idx >= 0 else None

    def create_var(self, name=None, **kw):
        if name is None:
            name = unique_name.generate('_generated_var')
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, name, **kw)
        self.vars[name] = v
        return v

    def create_parameter(self, name, shape, dtype, **kw):
        # Parameters live in the top (global) block, like the reference.
        global_block = self.program.global_block()
        p = Parameter(global_block, name, shape, dtype, **kw)
        global_block.vars[name] = p
        return p

    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("Variable %r not found in block %d or ancestors" %
                             (name, self.idx))
        return v

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def has_var_local(self, name):
        return name in self.vars

    def _find_var_recursive(self, name):
        b = self
        while b is not None:
            if name in b.vars:
                return b.vars[name]
            b = b.parent_block
        return None

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def append_op(self, type, inputs=None, outputs=None, attrs=None,
                  infer_shape=True):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        self.program._build_epoch += 1
        if infer_shape:
            from .core import registry
            registry.infer_shape(op, self)
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None,
                   infer_shape=True):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        self.program._build_epoch += 1
        if infer_shape:
            from .core import registry
            registry.infer_shape(op, self)
        return op

    def insert_op(self, index, type, inputs=None, outputs=None, attrs=None,
                  infer_shape=True):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        self.program._build_epoch += 1
        if infer_shape:
            from .core import registry
            registry.infer_shape(op, self)
        return op

    def remove_op(self, index):
        op = self.ops.pop(index)
        self.program._build_epoch += 1
        return op

    def __repr__(self):
        lines = ["Block %d (parent %d):" % (self.idx, self.parent_idx)]
        for op in self.ops:
            lines.append("  " + repr(op))
        return "\n".join(lines)


class Program(object):
    """A list of blocks; block 0 is global (ref: fluid/framework.py:1510)."""

    _uid_counter = [0]

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self._current_block_idx = 0
        self._seed = 0
        self.random_seed = 0
        self._version = 1
        # executor-side compile cache keys on (_uid, _build_epoch): the uid is
        # monotonic (id() can be reused after GC), the epoch bumps on every op
        # mutation so stale compiled step functions are never replayed.
        Program._uid_counter[0] += 1
        self._uid = Program._uid_counter[0]
        self._build_epoch = 0
        self._op_uid_counter = 0

    # -- block management -------------------------------------------------
    def global_block(self):
        return self.blocks[0]

    def block(self, idx):
        return self.blocks[idx]

    def current_block(self):
        return self.blocks[self._current_block_idx]

    @property
    def num_blocks(self):
        return len(self.blocks)

    def _create_block(self, parent_idx=None):
        parent = self._current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self._current_block_idx = b.idx
        return b

    def _rollback(self):
        self._current_block_idx = self.current_block().parent_idx

    # -- introspection ----------------------------------------------------
    def list_vars(self):
        for b in self.blocks:
            for v in b.vars.values():
                yield v

    def all_parameters(self):
        return self.global_block().all_parameters()

    def clone(self, for_test=False):
        """Deep-copy the program. for_test=True switches ops that behave
        differently at inference (dropout, batch_norm) into test mode
        (ref: fluid/framework.py Program.clone)."""
        p = copy.deepcopy(self)
        if for_test:
            for b in p.blocks:
                for op in b.ops:
                    if 'is_test' in _TEST_MODE_OPS.get(op.type, ()):
                        op.attrs['is_test'] = True
                    if op.type == 'dropout':
                        op.attrs['is_test'] = True
                    if op.type == 'batch_norm':
                        op.attrs['is_test'] = True
        return p

    def __deepcopy__(self, memo):
        p = Program.__new__(Program)
        memo[id(self)] = p
        p.blocks = []
        p._current_block_idx = self._current_block_idx
        p._seed = self._seed
        p.random_seed = self.random_seed
        p._version = self._version
        Program._uid_counter[0] += 1
        p._uid = Program._uid_counter[0]
        p._build_epoch = self._build_epoch
        p._op_uid_counter = self._op_uid_counter
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            p.blocks.append(nb)
        for b, nb in zip(self.blocks, p.blocks):
            for name, v in b.vars.items():
                cls = Parameter if isinstance(v, Parameter) else Variable
                nv = cls.__new__(cls)
                nv.__dict__.update({k: val for k, val in v.__dict__.items()
                                    if k != 'block'})
                nv.block = nb
                nb.vars[name] = nv
            for op in b.ops:
                nb.ops.append(Operator(nb, op.type,
                                       {k: list(v) for k, v in op.inputs.items()},
                                       {k: list(v) for k, v in op.outputs.items()},
                                       copy.deepcopy(op.attrs, memo)))
        return p

    def to_string(self, throw_on_error=False, with_details=False):
        return "\n".join(repr(b) for b in self.blocks)

    __repr__ = to_string
    __str__ = to_string


# ops whose attrs flip at clone(for_test=True)
_TEST_MODE_OPS = {
    'dropout': ('is_test',),
    'batch_norm': ('is_test',),
    'layer_norm': (),
}


# ---------------------------------------------------------------------------
# default program singletons + guards (ref: fluid/framework.py:2188-2256)
# ---------------------------------------------------------------------------
_main_program_ = Program()
_startup_program_ = Program()


def default_main_program():
    return _main_program_


def default_startup_program():
    return _startup_program_


def switch_main_program(program):
    global _main_program_
    prev, _main_program_ = _main_program_, program
    return prev


def switch_startup_program(program):
    global _startup_program_
    prev, _startup_program_ = _startup_program_, program
    return prev


_name_scopes = []


@contextlib.contextmanager
def name_scope(prefix):
    """Ops built inside carry the scope in attr 'op_namescope' (nested
    scopes joined by '/'; ref: fluid/framework.py name_scope), and their
    lowering runs under jax.named_scope of it (core/lowering.py run_op):
    in a device trace the op_name of what they emit reads
    '.../<prefix>/<op type>/...', so a sub-layer built from generic ops
    (a shared expert out of mul + swiglu) can be told apart."""
    _name_scopes.append(str(prefix))
    try:
        yield
    finally:
        _name_scopes.pop()


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)


# ---------------------------------------------------------------------------
# Places. The reference's Place is a C++ boost::variant
# (platform/place.h:79); here a Place selects the jax backend.
# ---------------------------------------------------------------------------
class Place(object):
    _kind = 'cpu'

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((self._kind, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


class CPUPlace(Place):
    _kind = 'cpu'


class TPUPlace(Place):
    _kind = 'tpu'


class CUDAPlace(Place):
    """Accepted for source compatibility; resolves to the accelerator backend
    (TPU here) — the reference's CUDAPlace (platform/place.h:54)."""
    _kind = 'tpu'


class CUDAPinnedPlace(Place):
    _kind = 'cpu'


def place_device(place):
    """Resolve a Place to the local jax device it names. TPUPlace/CUDAPlace
    mean the first local TPU device and CPUPlace the first cpu device —
    or RuntimeError naming the platforms that ARE present, never another
    backend in their stead. `place=None` is jax's default backend (or the
    core.config.set_backend() pin). local_devices: under multi-host,
    devices() is the GLOBAL list and entry 0 belongs to process 0 —
    single-device work must stay on a device THIS process owns."""
    import jax
    from .core.config import get_backend
    backend = get_backend() if place is None else place._kind
    try:
        return jax.local_devices(backend=backend)[0]
    except RuntimeError as e:
        raise RuntimeError(
            "%s needs a %r device, but this process has only %s (%s)"
            % (place if place is not None else 'set_backend(%r)' % backend,
               backend,
               sorted({d.platform for d in jax.local_devices()}), e)) \
            from None


def grad_var_name(name):
    return name + '@GRAD'


GRAD_SUFFIX = '@GRAD'
