"""Share of the traced window with no operation on the device, in the DCGAN backlog."""

from bench.metrics.readers import idle_share as read

LAYER = "device"
UNIT = "%"
MOVES = "gen_images_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
