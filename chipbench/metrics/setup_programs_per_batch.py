"""Programs compiled or loaded from the persistent cache in set-up, per
distinct batch: what each new batch shape costs a training job."""


def read(ctx):
    return ctx["setup_programs"] / ctx["distinct_batches"]
