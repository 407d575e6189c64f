"""Host time in ``GenServer``'s ``gen.admit`` spans per tick, in the DCGAN
backlog."""

from bench import program_trace

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gen_images_per_s"


def read(ctx):
    return program_trace.span_ms(ctx, "gen.admit")
