"""Host time per scheduler tick: the tick's span less the device busy time in it, in the DCGAN backlog."""

from bench.metrics.readers import host_ms_per_tick as read

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gen_images_per_s"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
