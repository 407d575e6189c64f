"""Least time of the Pallas-run convolutions over their device time, in the DCGAN backlog."""

from bench.metrics.readers import conv_roofline as read

LAYER = "kernels"
UNIT = "%"
MOVES = "gen_images_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
