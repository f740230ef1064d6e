"""Share of the batches the input chain handed to the step loop before the
loop asked for them: 100 times the mean ``ready`` argument of the
``pipeline.handoff`` spans (the place stage's hand-off in
``data/graph_pipeline.BatchChain``; 1 where no step was waiting on the
queue, 0 where one was) that start in the traced window."""

from chipbench import spans


def read(ctx):
    ready = [st["ready"] for _, _, st in
             spans.window_spans(ctx, "pipeline.handoff")]
    return 100 * sum(ready) / len(ready) if ready else None
