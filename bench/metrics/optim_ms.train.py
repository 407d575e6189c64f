"""Device time under ``train.optimizer`` per train step: the loss scaler's
check and AdamW."""

from bench import program_trace

LAYER = "model step"
UNIT = "ms"
MOVES = "train_step_ms"


def read(ctx):
    return program_trace.device_ms(ctx, "train.optimizer")
