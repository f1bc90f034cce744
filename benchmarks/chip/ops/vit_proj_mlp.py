"""The rest of a pre-LN encoder block: the attention output projection
and its residual, LayerNorm, the MLP (``d`` to ``mlp`` to ``d``, exact
GELU) and its residual: ``(tokens, d)`` to ``(tokens, d)``, 2 FLOPs per
MAC.  Its weights and the block's output count in the profile's m.  No
kernel of its own."""

from harness.costs import F32, OpCost


def cost(shape, op):
    t, d = shape
    f = op["mlp"]
    params = ((d * d + d) + 2 * d + (d * f + f) + (f * d + d)) * F32
    out = t * d * F32
    return (OpCost(2.0 * t * d * d + 4.0 * t * d * f, out, out, params,
                   params + out), (t, d))
