"""Share of the traced window with no operation on the device, in ENet frames."""

from bench.metrics.readers import idle_share as read

LAYER = "device"
UNIT = "%"
MOVES = "seg_frames_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
