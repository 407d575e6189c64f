"""Share of the traced window with no operation on the device, in open-loop DCGAN serving."""

from bench.metrics.readers import idle_share as read

LAYER = "device"
UNIT = "%"
MOVES = "gen_latency_p95_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
