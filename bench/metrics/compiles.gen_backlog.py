"""Executables built or loaded inside the window (``repro.compile`` spans), a
whole count, in the DCGAN backlog."""

from bench import program_trace

LAYER = "scheduler"
UNIT = "count"
MOVES = "gen_images_per_s"


def read(ctx):
    return program_trace.span_count(ctx, "repro.compile")
