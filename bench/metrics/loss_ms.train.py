"""Device time under ``train.loss`` per train step: the per-pixel loss and its
gradient."""

from bench import program_trace

LAYER = "model step"
UNIT = "ms"
MOVES = "train_step_ms"


def read(ctx):
    return program_trace.device_ms(ctx, "train.loss")
