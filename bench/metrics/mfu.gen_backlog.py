"""Useful FLOPs per second over the chip's bf16 peak, in the DCGAN backlog."""

from bench.metrics.readers import mfu as read

LAYER = "whole step"
UNIT = "%"
MOVES = "gen_images_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
