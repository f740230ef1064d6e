"""Seconds from the start of the run to the first window step: the data,
the Trainer, and one step on each batch the window uses, with every compile
or persistent-cache load it needs."""


def read(ctx):
    return ctx["setup_s"]
