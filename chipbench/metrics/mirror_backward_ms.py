"""Device milliseconds per window step, per chip, in the ELL aggregation's
mirror backward: the error rows' all-gather and the column-major walk,
with the pads and copies around them; operations whose ``tf_op`` path has
the segment ``mirror_backward`` (``jax.named_scope`` in
``distributed/aggregate._ell_bwd``)."""

from chipbench import spans


def read(ctx):
    return spans.scoped_ms(ctx, "mirror_backward")
