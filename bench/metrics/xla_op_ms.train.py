"""Device time of every non-Pallas operation per train step, in ENet training."""

from bench.metrics.readers import xla_op_ms as read

LAYER = "model step"
UNIT = "ms"
MOVES = "train_step_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
