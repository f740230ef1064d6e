"""The fullest chip's ``peak_bytes_in_use`` after the window, in GiB."""


def read(ctx):
    return ctx["peak_bytes"] / 2**30
