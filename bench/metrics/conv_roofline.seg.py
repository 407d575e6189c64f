"""Least time of the Pallas-run convolutions over their device time, in ENet frames."""

from bench.metrics.readers import conv_roofline as read

LAYER = "kernels"
UNIT = "%"
MOVES = "seg_frames_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
