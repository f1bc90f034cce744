"""A 2-D convolution with bias, stride 1, ``SAME`` or ``VALID`` padding:
``(h, w, c)`` to ``(ho, wo, cout)``.  2 FLOPs per MAC; the weights, the
activation read and the activation written count in the profile's m.  Its
time counts towards the ``conv`` roofline."""

from harness.costs import F32, OpCost


def cost(shape, op):
    h, w, c = shape
    k, cout = op["k"], op["cout"]
    if op["pad"] == "SAME":
        ho, wo = h, w
    else:
        ho, wo = h - k + 1, w - k + 1
    params = (k * k * c + 1) * cout * F32
    out = ho * wo * cout * F32
    inp = h * w * c * F32
    return (OpCost(2.0 * k * k * c * cout * ho * wo, inp, out, params,
                   params + out + inp, kernel="conv"), (ho, wo, cout))
