"""Share of the convolutions' roofline: the least time the chip could take
for every convolution of the window's launches (per conv the larger of its
FLOPs over peak FLOP/s and its bytes over HBM bytes/s, counted by the
benchmark's ``ops/conv.py``) over the device time of the trace's ops that
JAX's ``conv_general_dilated`` produced."""


def read(rec):
    tr = rec.get("trace")
    bound = rec.get("bound_s", {}).get("conv")
    if not tr or not bound:
        return None
    conv = tr["primitive_s"].get("conv_general_dilated", 0.0)
    return 100.0 * bound / conv if conv else None
