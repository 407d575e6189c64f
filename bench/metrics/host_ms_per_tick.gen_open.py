"""Host time per scheduler tick: the tick's span less the device busy time in it, in open-loop DCGAN serving."""

from bench.metrics.readers import host_ms_per_tick as read

LAYER = "scheduler"
UNIT = "ms"
MOVES = "gen_latency_p95_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
