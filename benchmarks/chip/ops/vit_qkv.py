"""The first part of a pre-LN encoder block: LayerNorm, then the query,
key and value projections with biases: ``(tokens, d)`` to ``(tokens,
3 d)``, 2 FLOPs per MAC.  Its weights and the block's input count in the
profile's m (the block's output counts in ``vit_proj_mlp``).  No kernel of
its own."""

from harness.costs import F32, OpCost


def cost(shape, op):
    t, d = shape
    params = (2 * d + 3 * (d * d + d)) * F32
    inp = t * d * F32
    return (OpCost(2.0 * t * d * 3 * d, inp, 3 * inp, params, params + inp),
            (t, 3 * d))
