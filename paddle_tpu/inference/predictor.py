"""Predictor serving API (ref: inference/api/analysis_predictor.cc:77-153,
paddle_api.h PaddlePredictor).

TPU-native equivalent of the reference pipeline (load -> IR analysis ->
NaiveExecutor): load -> prune to the feed/fetch subgraph -> jit. The
reference's analysis passes (conv+bn fold, fc fuse, TensorRT subgraphs)
are subsumed by XLA fusion; `clone(for_test)` semantics (BN/dropout in
inference mode) are applied at load when the model was saved from a train
program. The first run compiles (warmable via `warmup`); subsequent runs
hit the executor's compiled-step cache, the NaiveExecutor analogue.
"""
from __future__ import annotations

import os

import numpy as np


class Config(object):
    """AnalysisConfig equivalent: where the model lives + how to run it."""

    def __init__(self, model_dir=None, prog_file=None, params_file=None):
        self.model_dir = model_dir
        self.prog_file = prog_file
        self.params_file = params_file
        self.ref_format = None   # None = autodetect, True/False to force
        self._place = None

    def set_model(self, model_dir, params_file=None):
        self.model_dir = model_dir
        self.params_file = params_file

    def enable_tpu(self):
        from ..framework import TPUPlace
        self._place = TPUPlace()
        return self

    def disable_gpu(self):
        from ..framework import CPUPlace
        self._place = CPUPlace()
        return self


class Predictor(object):
    def __init__(self, config):
        from ..executor import Executor
        from ..core.scope import Scope
        self._config = config
        self._scope = Scope()
        self._exe = Executor(config._place)
        # bulk dispatches (run_batches) report as an inference source in
        # the profiler, not a training one
        self._exe._profile_role = 'infer'
        self._program, self._feed_names, self._fetch_vars = self._load()

    # -- loading -----------------------------------------------------------
    def _load(self):
        from ..core.scope import scope_guard
        from .. import io as ptpu_io
        from . import ref_format
        cfg = self._config
        dirname = cfg.model_dir
        model_file = cfg.prog_file
        ref = cfg.ref_format
        if ref is None:
            # autodetect: our save_inference_model writes JSON ('{' first);
            # the reference writes protobuf
            path = os.path.join(dirname, model_file or '__model__')
            with open(path, 'rb') as f:
                first = f.read(1)
            ref = first != b'{'
        with scope_guard(self._scope):
            if ref:
                return ref_format.load_reference_inference_model(
                    dirname, self._exe, model_filename=model_file,
                    params_filename=cfg.params_file, scope=self._scope)
            return ptpu_io.load_inference_model(
                dirname, self._exe, model_filename=model_file,
                params_filename=cfg.params_file)

    # -- serving -----------------------------------------------------------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return [v.name for v in self._fetch_vars if v is not None]

    def _normalize_feed(self, inputs):
        """List (feed order) or dict -> feed dict; shared by run() and
        run_batches()."""
        if isinstance(inputs, (list, tuple)):
            if len(inputs) != len(self._feed_names):
                raise ValueError(
                    "predictor expects %d inputs (%s), got %d"
                    % (len(self._feed_names), self._feed_names, len(inputs)))
            return dict(zip(self._feed_names, inputs))
        return dict(inputs)

    def run(self, inputs, return_numpy=True):
        """inputs: list (feed order) or dict name -> array/LoDTensor.
        Returns list of numpy outputs; return_numpy=False skips the host
        sync and returns device arrays (async serving loops sync once)."""
        from ..core.scope import scope_guard
        feed = self._normalize_feed(inputs)
        with scope_guard(self._scope):
            outs = self._exe.run(self._program, feed=feed,
                                 fetch_list=[v.name for v in
                                             self._fetch_vars
                                             if v is not None],
                                 return_numpy=return_numpy)
        if not return_numpy:
            return list(outs)
        return [np.asarray(o) for o in outs]

    def run_batches(self, batches, return_numpy=True):
        """Bulk offline/eval inference: run K pre-staged batches in ONE
        device dispatch (the Executor's multi-step lax.scan machinery,
        fetch_policy='stack'), amortizing the fixed per-dispatch cost
        across all K — per-batch results are bit-identical to K
        sequential `run()` calls.

        batches: list of K per-batch inputs, each a list (feed order) or
        dict name -> array/LoDTensor exactly as `run()` takes; every
        batch must share one compiled shape (LoD batches one bucket).
        Returns a list of K per-batch output lists."""
        from ..core.scope import scope_guard
        batches = list(batches)
        if not batches:
            return []
        feeds = [self._normalize_feed(b) for b in batches]
        missing = [n for n in self._feed_names
                   if any(n not in f for f in feeds)]
        if missing:
            raise ValueError("batches missing feeds: %r (predictor "
                             "expects %s)" % (missing, self._feed_names))
        grouped = {n: [f[n] for f in feeds] for n in self._feed_names}
        with scope_guard(self._scope):
            outs = self._exe.run_steps(
                self._program, feed=grouped,
                fetch_list=[v.name for v in self._fetch_vars
                            if v is not None],
                fetch_policy='stack', return_numpy=return_numpy)
        k = len(batches)
        return [[o[i] if not return_numpy else np.asarray(o[i])
                 for o in outs] for i in range(k)]

    def warmup(self, sample_inputs):
        """Compile ahead of serving (the reference predictor's Prepare)."""
        self.run(sample_inputs)
        return self

    def clone(self):
        """A predictor sharing this one's weights (ref scope sharing for
        multi-thread serving, analysis_predictor.cc Clone)."""
        twin = Predictor.__new__(Predictor)
        twin._config = self._config
        twin._scope = self._scope           # shared weights
        twin._exe = self._exe               # shared compiled cache
        twin._program = self._program
        twin._feed_names = self._feed_names
        twin._fetch_vars = self._fetch_vars
        return twin


def create_predictor(config):
    return Predictor(config)
