"""Device time of the Pallas (Mosaic) kernels per frame, in ENet frames."""

from bench.metrics.readers import pallas_ms as read

LAYER = "kernels"
UNIT = "ms"
MOVES = "seg_frames_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
