"""Device milliseconds per window step, per chip, in the combination matmul
``h @ W`` and its transpose: operations whose ``tf_op`` path has the
segment ``combine`` (``jax.named_scope`` in
``EngineBundle._forward_local``)."""

from chipbench import spans


def read(ctx):
    return spans.scoped_ms(ctx, "combine")
