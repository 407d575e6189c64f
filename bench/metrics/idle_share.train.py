"""Share of the traced window with no operation on the device, in ENet training."""

from bench.metrics.readers import idle_share as read

LAYER = "device"
UNIT = "%"
MOVES = "train_step_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
