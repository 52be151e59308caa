"""Host time per block of a bulk stream inside the engine's stages:
``stream:flatten`` + ``stream:upload`` + ``stream:download`` +
``stream:unflatten``, each stage's mean over its spans in the tracer's
ring, summed (``stages``)."""
from chipbench import stages


def read(ctx):
    return stages.us_per_block(ctx.spans, stages.HOST)
