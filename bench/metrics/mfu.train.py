"""Useful FLOPs per second over the chip's bf16 peak, in ENet training."""

from bench.metrics.readers import mfu as read

LAYER = "whole step"
UNIT = "%"
MOVES = "train_step_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
