"""Milliseconds per window step that the training loop waited for its next
batch (``Trainer.stall_per_step``): host work the device step did not
hide."""


def read(ctx):
    return ctx["window"]["stall_s"] * 1e3
