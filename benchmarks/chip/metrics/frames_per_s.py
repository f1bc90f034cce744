"""Frames whose outputs reached the host in the window, over its seconds."""


def read(rec):
    return rec["frames_done"] / rec["window_s"] if "frames_done" in rec \
        else None
