"""Shared machinery of the chip benchmark: device checks, the copied
traffic and cost arithmetic, trace reduction and the result line."""
