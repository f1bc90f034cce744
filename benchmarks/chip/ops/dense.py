"""A dense layer with bias over the flattened activation: ``(h, w, c)`` to
``(1, 1, out)``, 2 FLOPs per MAC; weights, input and output count in the
profile's m."""

from harness.costs import F32, OpCost


def cost(shape, op):
    h, w, c = shape
    fi, fo = h * w * c, op["out"]
    params = (fi + 1) * fo * F32
    return (OpCost(2.0 * fi * fo, fi * F32, fo * F32, params,
                   params + fo * F32 + fi * F32), (1, 1, fo))
