"""Least time of the Pallas-run convolutions over their device time, in ENet training."""

from bench.metrics.readers import conv_roofline as read

LAYER = "kernels"
UNIT = "%"
MOVES = "train_step_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
