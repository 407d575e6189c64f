"""Device time under the program's ``engine.dense`` scope per frame, Pallas
kernels and their XLA passes, in ENet frames."""

from bench import program_trace

LAYER = "kernels"
UNIT = "ms"
MOVES = "seg_frames_per_s"


def read(ctx):
    return program_trace.device_ms(ctx, "engine.dense")
