"""Share of the HBM roofline that the device's busy time reached on the
traced slice: the least time the chip needs for the slice's device-leg
bytes, input read and output written once (``roofline.py``, peaks from
``peaks.json``), over the time in which any operation ran on the device:
relayouts and copies included, whatever implements the codec. The bytes
are those of the slice alone (the device leg's counter read when the
profiler started and when it stopped), never the window's."""

import roofline
from cluster import leg_delta


def read(ctx, args: dict):
    s = ctx.slice
    trace = s.get("trace") or {}
    if not trace.get("busy_s") or not ctx.peaks:
        return None
    nbytes = leg_delta(s["before"], s["after"]).get("device", 0)
    if nbytes <= 0:
        return None
    g = ctx.cfg["geometry"]
    moved = roofline.codec_bytes(args["work"], nbytes, g["data_shards"],
                                 g["parity_shards"],
                                 ctx.result.get("lost_shards", 0))
    return 100.0 * roofline.least_seconds(moved, ctx.peaks) / trace["busy_s"]
