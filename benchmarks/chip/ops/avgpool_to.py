"""Adaptive average pooling to ``size`` x ``size``: ``(h, w, c)`` to
``(size, size, c)``.  Counted as free, as the program's profile counts
it."""

from harness.costs import F32, OpCost


def cost(shape, op):
    h, w, c = shape
    s = op["size"]
    return OpCost(0.0, h * w * c * F32, s * s * c * F32, 0.0, 0.0), (s, s, c)
