"""A ``k`` x ``k`` max pool, stride ``k``: ``(h, w, c)`` to
``(h // k, w // k, c)``, one op per window element."""

from harness.costs import F32, OpCost


def cost(shape, op):
    h, w, c = shape
    k = op["k"]
    ho, wo = h // k, w // k
    out = ho * wo * c * F32
    inp = h * w * c * F32
    return OpCost(1.0 * k * k * c * ho * wo, inp, out, 0.0, out + inp), (
        ho, wo, c)
