"""Device time of the decomposition's layout passes (``layout.*`` scopes) per
frame, in ENet frames."""

from bench import program_trace

LAYER = "kernels"
UNIT = "ms"
MOVES = "seg_frames_per_s"


def read(ctx):
    return program_trace.device_ms(ctx, "layout")
