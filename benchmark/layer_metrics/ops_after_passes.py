"""Pass pipeline: op count of the main program after the default
optimisation pipeline (bench.py:_pass_ops arithmetic)."""


def reduce(run):
    from paddle_tpu import passes
    runner = run['runner']
    opt, _ = passes.apply_optimization_pipeline(
        runner.main, fetch_names=[runner.loss.name])
    return sum(len(b.ops) for b in opt.blocks)
