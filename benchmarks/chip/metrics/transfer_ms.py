"""Transfer per frame: the transport's own ``TransferRecord.serialize_s``
summed over the window, over the frames completed."""


def read(rec):
    n = rec.get("frames_done")
    return rec["transfer_s"] / n * 1e3 if n else None
