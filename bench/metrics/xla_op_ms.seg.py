"""Device time of every non-Pallas operation per frame, in ENet frames."""

from bench.metrics.readers import xla_op_ms as read

LAYER = "model step"
UNIT = "ms"
MOVES = "seg_frames_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
