"""Program → JAX tracer.

This is the heart of the framework and replaces the reference's C++
op-interpreter hot loop (framework/executor.cc:203 Executor::Run →
operator.cc:913 OperatorWithKernel::RunImpl). Instead of interpreting
OpDescs per step, we walk a Block ONCE inside a jax trace, turning each op
into XLA ops via its registered lowering; jit compiles the whole step and XLA
owns fusion/layout/memory (subsuming the reference's fusion-pass zoo,
framework/ir/, and allocator stack, memory/).

The traced function is pure: (state, feed, rng) -> (fetches, new_state).
`state` carries every persistable var (params, optimizer moments, LR
counters) — the functional equivalent of the reference's mutable Scope
(framework/scope.h:48). In-place ops (sgd writes ParamOut==Param) become env
rebinding; the executor commits new_state back to the host Scope after each
run.

Gradient ops: append_backward emits `<type>_grad` OpDescs. If no explicit
lowering is registered for a grad op, `_lower_generic_grad` re-lowers the
forward op under jax.vjp and applies the output cotangents — per-op autodiff
parity (ref GradOpDescMaker) without per-op grad code. The recomputed
forward is CSE'd by XLA against the original (same trace, same inputs) —
EXCEPT inside remat_segment sub-blocks, whose lowering wraps the trace in
jax.checkpoint (optimization-barrier-guarded), so segment interiors really
recompute in the backward instead of staying live (passes/recompute.py).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from . import registry
from .lod import LoDArray, unwrap
from ..framework import is_float_dtype


class TraceError(RuntimeError):
    pass


class OpCtx(object):
    """Per-op context handed to lowering rules."""

    __slots__ = ('tracer', 'op', 'attrs', 'block', 'abstract')

    def __init__(self, tracer, op, block):
        self.tracer = tracer
        self.op = op
        self.attrs = op.attrs
        self.block = block
        self.abstract = False

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    @property
    def is_test(self):
        return bool(self.attrs.get('is_test', False))

    def rng(self):
        # seeded ops fold the user seed into the per-step key: deterministic
        # given (program seed, step, op seed) but fresh each step — matching
        # the reference, which seeds a generator once and draws per step.
        seed = self.attrs.get('seed', 0) or self.attrs.get('_fwd_seed', 0)
        if seed:
            return jax.random.fold_in(self.tracer.step_key,
                                      int(seed) & 0x7FFFFFFF)
        uid = self.attrs.get('_fwd_op_uid', self.attrs.get('_op_uid', 0))
        return jax.random.fold_in(self.tracer.step_key, int(uid) & 0x7FFFFFFF)

    def var(self, name):
        """Compile-time Variable metadata (shape with -1s, dtype, lod_level)."""
        return self.block._find_var_recursive(name)

    def env(self, name):
        return self.tracer.env[name]

    def run_block(self, block_idx, env):
        """Run a sub-block (control flow) against an explicit env dict."""
        sub = self.tracer.program.block(block_idx)
        self.tracer.run_block(sub, env)
        return env


class _FusedActOp(object):
    """Shadow op handed to an activation lowering when it runs fused into
    its producer (fuse_act attr): carries the activation's original attrs
    plus the producer's uid for any rng bookkeeping."""

    __slots__ = ('type', 'attrs', 'inputs', 'outputs')

    def __init__(self, act_type, act_attrs, producer):
        self.type = act_type
        self.attrs = dict(act_attrs)
        self.attrs.setdefault('_op_uid', producer.attrs.get('_op_uid', 0))
        self.inputs = {}
        self.outputs = {}


class Tracer(object):
    """Walks blocks, maintaining env: var name -> traced value."""

    def __init__(self, program, step_key, scope_types=None):
        self.program = program
        self.step_key = step_key
        self.env = {}
        self.fetches = []
        self.written = set()
        # static (host) side-channels: sequence_pad records per-seq lengths
        # so sequence_unpad can rebuild a static lod; assign_value records
        # its host constant so ops needing trace-time values (e.g.
        # sequence_slice offsets) can read them even under jit
        self.static_lengths = {}
        self.host_consts = {}
        # (op type, body) from each op that picks between bodies as it
        # lowers (kv_block_attention: 'kernel' | 'jnp'); export_decode
        # writes them into the artifact's signature
        self.lowered_bodies = []

    def read(self, name, op):
        if name in self.env:
            return self.env[name]
        raise TraceError(
            "Op %s reads variable %r which has no value. Feed it, initialize "
            "it via the startup program, or check op ordering." % (op, name))

    def write(self, name, value):
        self.env[name] = value
        self.written.add(name)

    def run_block(self, block, env=None):
        if env is not None:
            saved, self.env = self.env, env
        try:
            for op in block.ops:
                self.run_op(op, block)
        finally:
            if env is not None:
                self.env = saved
        return self.env

    def run_op(self, op, block):
        t = op.type
        if t == 'feed':
            return  # env pre-populated by executor
        if t == 'fetch':
            self.fetches.append(self.read(op.inputs['X'][0], op))
            return
        # every HLO op this lowering emits carries the Fluid op's type in
        # its op_name metadata ('jit(train_step)/.../conv2d/...'), so a
        # device trace's ops can be traced back to the op that made them;
        # trace-time only, nothing at run time
        # (under fluid.name_scope: '<scope>/<op type>/...')
        scope = op.attrs.get('op_namescope')
        with jax.named_scope('%s/%s' % (scope, t) if scope else t):
            return self._lower_op(op, block)

    def _lower_op(self, op, block):
        t = op.type
        d = registry.get(t)
        if d is None:
            if t.endswith('_grad'):
                fwd = registry.get(t[:-5])
                if fwd is not None:
                    return self._lower_generic_grad(op, block, fwd)
            raise TraceError("No lowering registered for op type %r (%s)" %
                             (t, op))
        ctx = OpCtx(self, op, block)
        ins = self._gather_inputs(op, block)
        src_la = None
        src_rows = None
        if d.lod_mode != 'aware':
            for vals in ins.values():
                for v in vals:
                    if isinstance(v, LoDArray) and src_la is None:
                        src_la = v
                        src_rows = v.data.shape[0] if v.data.ndim else None
            if src_la is not None:
                ins = {slot: [unwrap(v) for v in vals]
                       for slot, vals in ins.items()}
        outs = d.lower(ctx, ins)
        if op.attrs.get('fuse_act'):
            outs = self._apply_fused_act(op, block, outs)
        if (d.lod_mode == 'pass' and src_la is not None and outs):
            outs = {slot: [self._maybe_wrap(v, src_la, src_rows)
                           for v in vals] if vals is not None else None
                    for slot, vals in outs.items()}
        self._scatter_outputs(op, outs)

    def _apply_fused_act(self, op, block, outs):
        """Apply a pass-fused activation (passes/fuse_act.py) to the
        producer's primary output, inside the same traced expression:
        the activation's own registered lowering runs on the slot value,
        so fused and unfused programs are bit-identical."""
        act = op.attrs['fuse_act']
        slot = op.attrs.get('fuse_act_slot', 'Out')
        d = registry.get(act)
        if d is None:
            raise TraceError(
                "op %s carries fuse_act=%r but no lowering is registered "
                "for that activation" % (op, act))
        vals = (outs or {}).get(slot)
        if not vals or vals[0] is None:
            raise TraceError(
                "op %s carries fuse_act=%r but produced no value in slot "
                "%r to activate" % (op, act, slot))
        shadow = _FusedActOp(act, op.attrs.get('fuse_act_attrs', {}), op)
        ctx = OpCtx(self, shadow, block)
        with jax.named_scope(act):
            acted = d.lower(ctx, {'X': [unwrap(vals[0])]})['Out'][0]
        outs = dict(outs)
        outs[slot] = [acted] + list(vals[1:])
        return outs

    @staticmethod
    def _maybe_wrap(v, src_la, rows):
        # ShareLoD: rewrap row-aligned outputs with the source's lod,
        # preserving its static/traced mode
        if (v is not None and not isinstance(v, LoDArray)
                and hasattr(v, 'ndim') and v.ndim >= 1 and rows is not None
                and v.shape[0] == rows):
            return src_la.with_lod_of(v)
        return v

    def _gather_inputs(self, op, block):
        ins = {}
        for slot, names in op.inputs.items():
            ins[slot] = [self.read(n, op) if n else None for n in names]
        return ins

    def _scatter_outputs(self, op, outs):
        if outs is None:
            outs = {}
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            for n, v in zip(names, vals):
                if n and v is not None:
                    self.write(n, v)

    # ------------------------------------------------------------------
    # Generic VJP-derived gradient lowering.
    # Grad op convention (see backward.py):
    #   attrs['_fwd_inputs']  : {slot: [names]} of the forward op
    #   attrs['_fwd_outputs'] : {slot: [names]}
    #   attrs['_out_grad_map']: {fwd_out_name: grad_var_name or ''}
    #   attrs['_in_grad_map'] : {fwd_in_name: grad_var_name or ''}
    #   attrs['_fwd_op_uid']  : uid of the forward op (rng consistency)
    # ------------------------------------------------------------------
    def _lower_generic_grad(self, op, block, fwd_def):
        a = op.attrs
        fwd_inputs = a['_fwd_inputs']
        fwd_outputs = a['_fwd_outputs']
        out_grad_map = a['_out_grad_map']
        in_grad_map = a['_in_grad_map']

        ctx = OpCtx(self, op, block)

        # names to differentiate with respect to (deduped, order-stable)
        diff_names = []
        for slot, names in fwd_inputs.items():
            for n in names:
                if n and in_grad_map.get(n) and n not in diff_names:
                    diff_names.append(n)
        if not diff_names:
            return

        aware = fwd_def.lod_mode == 'aware'
        base_env = {}
        for slot, names in fwd_inputs.items():
            for n in names:
                if n:
                    v = self.read(n, op)
                    base_env[n] = v if aware else unwrap(v)

        # float forward outputs participate in the vjp
        float_outs = []
        for slot, names in fwd_outputs.items():
            for n in names:
                if n and n not in float_outs:
                    v = block._find_var_recursive(n)
                    if v is None or is_float_dtype(v.dtype):
                        float_outs.append(n)

        def f(diff_vals):
            env2 = dict(base_env)
            for n, v in zip(diff_names, diff_vals):
                orig = base_env.get(n)
                if isinstance(orig, LoDArray):
                    v = orig.with_lod_of(v)
                env2[n] = v
            ins = {slot: [env2.get(n) if n else None for n in names]
                   for slot, names in fwd_inputs.items()}
            outs = fwd_def.lower(ctx, ins)
            out_env = {}
            for slot, names in fwd_outputs.items():
                vals = (outs or {}).get(slot)
                if vals is None:
                    continue
                for n, v in zip(names, vals):
                    if n and v is not None:
                        out_env[n] = unwrap(v)
            return {n: out_env[n] for n in float_outs if n in out_env}

        diff_vals = [unwrap(base_env[n]) for n in diff_names]
        primals, vjp_fn = jax.vjp(f, diff_vals)

        cots = {}
        for n, p in primals.items():
            gname = out_grad_map.get(n, '')
            if gname and gname in self.env:
                g = unwrap(self.env[gname])
                if g.dtype != p.dtype:
                    g = g.astype(p.dtype)
                if g.shape != p.shape:
                    if np.prod(g.shape) == np.prod(p.shape):
                        g = g.reshape(p.shape)
                    else:
                        g = jnp.broadcast_to(g, p.shape)
                cots[n] = g
            else:
                cots[n] = jnp.zeros(p.shape, p.dtype)
        (in_grads,) = vjp_fn(cots)

        for n, g in zip(diff_names, in_grads):
            gname = in_grad_map.get(n, '')
            if gname:
                self.write(gname, g)
