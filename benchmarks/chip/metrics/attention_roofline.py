"""Share of the attention kernel's roofline: the least time the chip could
take for the attention core of every encoder block of the window's
launches (per block the larger of its FLOPs over peak FLOP/s and its bytes
over HBM bytes/s, counted by the benchmark's ``ops/attention.py``) over the
device time of the trace's Pallas ops, which in the ViT stage closures are
the flash attention kernel alone (``tests/test_attention.py``)."""


def read(rec):
    tr = rec.get("trace")
    bound = rec.get("bound_s", {}).get("attention")
    if not tr or not bound:
        return None
    kernel = tr["primitive_s"].get("pallas_call", 0.0)
    return 100.0 * bound / kernel if kernel else None
