"""The whole step's share of the chip's peak while it ran: the FLOPs of
every stage launch of the window's rounds (the benchmark's count, 2 per
MAC) over the seconds in which an op ran on the device, from the trace,
times peak FLOP/s."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["busy_s"] or not rec.get("launch_flops"):
        return None
    return 100.0 * rec["launch_flops"] / (tr["busy_s"]
                                          * rec["peaks"]["flops_per_s"])
