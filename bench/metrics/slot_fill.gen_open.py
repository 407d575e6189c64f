"""Occupied slots per dispatched slot: substeps / (device_steps x batch), in open-loop DCGAN serving."""

from bench.metrics.readers import slot_fill as read

LAYER = "scheduler"
UNIT = "%"
MOVES = "gen_latency_p95_ms"

__all__ = ["LAYER", "UNIT", "MOVES", "read"]
