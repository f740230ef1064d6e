"""Mean milliseconds of the producer's ``engine.layout`` spans that start
in the traced window: the feature cast, the partition, the format's
tables and the labels (``EngineBundle.prepare_batch``)."""

from chipbench import spans


def read(ctx):
    return spans.mean_span_ms(ctx, "engine.layout")
