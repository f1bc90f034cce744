"""The attention core of an encoder block, non-causal over every token:
``(tokens, 3 d)`` (q, k and v) to ``(tokens, d)``.  QKᵀ and PV, 2 FLOPs
per MAC each, 4·T²·d over the heads; it reads q, k and v and writes o, and
holds no weights: nothing of it counts in the profile's m beyond its
block's.  Its time counts towards the ``attention`` roofline."""

from harness.costs import F32, OpCost


def cost(shape, op):
    t, d3 = shape
    d = d3 // 3
    return (OpCost(4.0 * t * t * d, t * d3 * F32, t * d * F32, 0.0, 0.0,
                   kernel="attention"), (t, d))
