"""Host<->device transfer time per block of a bulk stream:
``stream:upload`` (pad, upload, dispatch) + ``stream:download`` (the copy
back), each stage's mean over its spans in the tracer's ring, summed
(``stages``)."""
from chipbench import stages


def read(ctx):
    return stages.us_per_block(ctx.spans, stages.TRANSFER)
