"""Mean milliseconds of the producer's ``pipeline.gather`` spans that
start in the traced window: the frontier's feature rows gathered
(``data/graph_pipeline.gather_features``)."""

from chipbench import spans


def read(ctx):
    return spans.mean_span_ms(ctx, "pipeline.gather")
