"""Host time in ``gen.dispatch`` spans (the lane's jitted call) per tick, in
the DCGAN backlog."""

from bench import program_trace

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gen_images_per_s"


def read(ctx):
    return program_trace.span_ms(ctx, "gen.dispatch")
