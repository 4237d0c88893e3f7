"""User-facing sharding annotations (TPU-native extension).

The reference's tensor-model-parallelism story was layer-device placement in
the legacy stack (ParallelNeuralNetwork.h:34) and sharded embedding tables on
pservers (distribute_transpiler.py:1012). Here both collapse into GSPMD
partition specs on parameters: annotate, and XLA partitions the matmuls and
inserts the collectives (all-gather/reduce-scatter over ICI).
"""
from __future__ import annotations

from ..framework import Variable
from .mesh import MODEL_AXIS, EXPERT_AXIS


def shard_parameter(param, spec):
    """Attach a partition spec to a parameter.

    spec: tuple with one entry per tensor dim — a mesh axis name to shard
    that dim over, or None to replicate it. e.g. for an fc weight [in, out]:
    shard_parameter(w, (None, 'mp')) = column-parallel (Megatron-style).
    """
    assert isinstance(param, Variable)
    param.sharding_spec = tuple(spec)
    return param


def shard_embedding(param, axis=0, mesh_axis=EXPERT_AXIS):
    """Shard an embedding table over a mesh axis (row-sharded vocab) — the
    dist-lookup-table capability (SURVEY §2.3): XLA turns the gathers into
    all-to-all traffic on the mesh."""
    spec = [None] * len(param.shape)
    spec[axis] = mesh_axis
    return shard_parameter(param, spec)


class MultiStepTrainer(object):
    """Multi-step training dispatch driver (the training-side counterpart
    of inference.BatchingPredictor, with a CompiledTrainer-style surface):
    owns the executor, the steps-per-dispatch policy, and the epoch loop
    with EOF tail flushing over Executor.run_steps — one device dispatch
    advances optimizer state K steps, so dispatch-bound workloads divide
    the per-run() floor by K (PERF_NOTES.md "Training dispatch floor").

        trainer = MultiStepTrainer(main_prog, steps_per_dispatch=16,
                                   fetch_list=[loss])
        trainer.startup(startup_prog)
        reader.prefetch_to_device(16)          # optional fast path
        for fetches in trainer.iter_epoch(reader):
            ...                                # one entry per DISPATCH
    """

    def __init__(self, program, steps_per_dispatch=8, fetch_list=None,
                 fetch_policy='final', place=None, scope=None,
                 executor=None, checkpoint=None, preemptible=False):
        from ..executor import Executor
        if int(steps_per_dispatch) < 1:
            raise ValueError("steps_per_dispatch must be >= 1, got %d"
                             % int(steps_per_dispatch))
        self.program = program
        self.steps_per_dispatch = int(steps_per_dispatch)
        self.fetch_list = list(fetch_list or [])
        self.fetch_policy = fetch_policy
        self.scope = scope
        self.executor = executor if executor is not None \
            else Executor(place)
        # fault-tolerance policy (core/checkpoint.py): evaluated at every
        # dispatch boundary; startup() restores from the newest committed
        # checkpoint so a SIGKILLed trainer resumes where it stopped
        self.checkpoint = checkpoint
        # preemptible=True routes SIGTERM (the scheduler's preemption
        # notice) to a graceful drain: run_steps writes one final
        # checkpoint at the next step boundary and exits 0 — a clean
        # resume instead of a crash (requires checkpoint=)
        self.preemptible = bool(preemptible)
        self.resume_info = None

    def startup(self, startup_program):
        """Run the startup program so every state var the K-step scan
        carries is materialized (run_steps refuses to create scan-carry
        entries mid-loop). With a checkpoint manager attached, then
        restore from the newest fully-committed checkpoint when one
        exists — kill-and-resume is the SAME script run twice. Returns
        self; resume_info/resume_step tell whether (and where) a restore
        happened."""
        self.executor.run(startup_program, scope=self.scope)
        if self.checkpoint is not None:
            if self.preemptible:
                from ..core import checkpoint as _ckpt
                _ckpt.install_preemption_handler()
            self.resume_info = self.checkpoint.restore(
                executor=self.executor, program=self.program,
                scope=self.scope)
        return self

    @property
    def resume_step(self):
        """Steps already trained before this incarnation (0 on a cold
        start)."""
        return int(self.resume_info['step']) if self.resume_info else 0

    def step_group(self, feed=None, reader=None, steps=None):
        """One dispatch of up to steps_per_dispatch steps; returns the
        fetches per fetch_policy ('final': last step only; 'stack':
        [K, ...] per fetch)."""
        return self.executor.run_steps(
            self.program, reader=reader, feed=feed,
            fetch_list=self.fetch_list,
            steps=int(steps) if steps is not None
            else self.steps_per_dispatch,
            scope=self.scope, fetch_policy=self.fetch_policy,
            checkpoint=self.checkpoint)

    def iter_epoch(self, reader):
        """Drive one epoch from a PyReader, yielding fetches per dispatch;
        starts the reader when needed, flushes the EOF tail group through
        its smaller compiled bucket, and resets the reader on exit. With
        a sharded/pooled reader decorated in (reader/sharded.py), the
        feeder-side counters land in profiler.training_report() next to
        this loop's host-stall column."""
        from ..core import EOFException
        # start when never started OR drained; the reader rejoins its
        # feeder thread the moment EOF is consumed (pipeline._pop), so
        # repeated sessions never accumulate dead threads and a drained
        # reader is indistinguishable from a fresh one here
        if getattr(reader, '_thread', None) is None \
                or getattr(reader, '_closed', True):
            reader.start()
        try:
            while True:
                try:
                    yield self.step_group(reader=reader)
                except EOFException:
                    return
        finally:
            reader.reset()

    @property
    def stats(self):
        """Per-dispatch counters (dispatches, steps, tail_flushes,
        host_stall_s) — also surfaced by profiler.training_report()."""
        return dict(self.executor._dispatch_stats)
