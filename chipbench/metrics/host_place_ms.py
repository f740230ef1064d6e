"""Mean milliseconds of the producer's ``engine.place`` spans that start
in the traced window: the host call that places the batch's leaves
(``EngineBundle.commit_batch``); the transfer completes asynchronously."""

from chipbench import spans


def read(ctx):
    return spans.mean_span_ms(ctx, "engine.place")
