"""``(h, w, c)`` to ``(1, 1, h * w * c)``; no work and no memory."""

from harness.costs import F32, OpCost


def cost(shape, op):
    h, w, c = shape
    return OpCost(0.0, 0.0, h * w * c * F32, 0.0, 0.0), (1, 1, h * w * c)
