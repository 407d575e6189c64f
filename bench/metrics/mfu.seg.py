"""Useful FLOPs per second over the chip's bf16 peak, in ENet frames."""

from bench.metrics.readers import mfu as read

LAYER = "whole step"
UNIT = "%"
MOVES = "seg_frames_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
