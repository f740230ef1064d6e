"""Mean milliseconds of the producer's ``pipeline.sample`` spans that
start in the traced window: the neighbour draw, the per-hop COO build and
the labels (``data/graph_pipeline.sample_batch``)."""

from chipbench import spans


def read(ctx):
    return spans.mean_span_ms(ctx, "pipeline.sample")
