"""Device time of the decomposition's layout passes (``layout.*`` scopes),
forward and backward, per train step."""

from bench import program_trace

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_step_ms"


def read(ctx):
    return program_trace.device_ms(ctx, "layout")
