"""Device time of the Pallas (Mosaic) kernels per scheduler tick, in the DCGAN backlog."""

from bench.metrics.readers import pallas_ms as read

LAYER = "kernels"
UNIT = "ms"
MOVES = "gen_images_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
