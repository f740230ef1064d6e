"""Mean MiB a batch places on the devices: the ``bytes`` argument of the
``engine.place`` spans (``EngineBundle.commit_batch``, every leaf placed)
that start in the traced window, over 2^20."""

from chipbench import spans


def read(ctx):
    placed = [st["bytes"] for _, _, st in
              spans.window_spans(ctx, "engine.place")]
    return sum(placed) / len(placed) / 2**20 if placed else None
