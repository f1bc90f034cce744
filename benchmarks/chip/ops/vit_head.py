"""ViT head: LayerNorm of the class token, then a linear classifier:
``(tokens, d)`` to ``(1, 1, out)``, 2 FLOPs per MAC.  Its weights, the
sequence read and the logits written count in the profile's m."""

from harness.costs import F32, OpCost


def cost(shape, op):
    t, d = shape
    fo = op["out"]
    params = (2 * d + d * fo + fo) * F32
    inp, out = t * d * F32, fo * F32
    return (OpCost(2.0 * d * fo, inp, out, params, params + inp + out),
            (1, 1, fo))
