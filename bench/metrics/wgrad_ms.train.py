"""Device time of the weight gradients (the ``grad.dw`` tap correlations) per
train step."""

from bench import program_trace

LAYER = "model step"
UNIT = "ms"
MOVES = "train_step_ms"


def read(ctx):
    return program_trace.device_ms(ctx, "grad.dw")
