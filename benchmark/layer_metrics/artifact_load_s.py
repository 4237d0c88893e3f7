"""Export / AOT: seconds for DecodingPredictor(artifact) plus the warm-up
request on a cached artifact (benchmark-side span)."""


def reduce(run):
    return run['ctx'].spans.durations('artifact_load')[0]
