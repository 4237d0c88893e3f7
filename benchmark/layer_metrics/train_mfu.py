"""Whole model: samples/s x required FLOPs per sample / (chips x peak bf16
FLOP/s). Recompute not counted. End-to-end utilisation: it is blind to
where the time goes and is not a kernel's roofline share."""


def reduce(run):
    ctx = run['ctx']
    return (100.0 * run['result']['samples_per_s']
            * ctx.model.flops_per_sample(ctx.cfg)
            / (ctx.chips * ctx.peaks['bf16_flops_per_s']))
