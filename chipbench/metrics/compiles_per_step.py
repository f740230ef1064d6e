"""Backend compiles in the window per window step (JAX's
``backend_compile_duration`` events).  Set-up runs every batch the window
uses, so this reads 0 unless a step compiles something per call."""


def read(ctx):
    return ctx["window_compiles"] / ctx["steps"]
