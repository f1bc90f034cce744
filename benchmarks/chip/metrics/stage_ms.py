"""Stage execution per frame: the engine's own ``StageTiming.wall_s``
summed over the window, over the frames completed."""


def read(rec):
    n = rec.get("frames_done")
    return rec["stage_s"] / n * 1e3 if n else None
