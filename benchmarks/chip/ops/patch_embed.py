"""ViT patch embedding: ``(h, w, c)`` to ``(tokens, d)``.  The frame is
cropped to whole ``patch`` x ``patch`` patches, each projected to ``d``
(2 FLOPs per MAC), the class token prepended and the position table of
``tokens`` rows added.  Its weights (kernel, bias, class token, position
table), the frame read and the sequence written count in the profile's m.
No kernel of its own."""

from harness.costs import F32, OpCost


def cost(shape, op):
    h, w, c = shape
    p, d = op["patch"], op["d"]
    n = (h // p) * (w // p)
    params = ((p * p * c + 1) * d + d + (n + 1) * d) * F32
    inp, out = h * w * c * F32, (n + 1) * d * F32
    return (OpCost(2.0 * n * p * p * c * d, inp, out, params,
                   params + inp + out), (n + 1, d))
