"""Batch roots trained per second over the whole window: every step that
ran in it, from the first one's start to the last one's end (host clock),
input pipeline included."""


def read(ctx):
    return ctx["steps"] * ctx["traffic"]["batch_size"] / ctx["window"]["seconds"]
