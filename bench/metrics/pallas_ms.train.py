"""Device time of the Pallas (Mosaic) kernels per train step, in ENet training."""

from bench.metrics.readers import pallas_ms as read

LAYER = "kernels"
UNIT = "ms"
MOVES = "train_step_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
