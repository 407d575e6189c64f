"""Device time of every non-Pallas operation per scheduler tick, in the DCGAN backlog."""

from bench.metrics.readers import xla_op_ms as read

LAYER = "model step"
UNIT = "ms"
MOVES = "gen_images_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
