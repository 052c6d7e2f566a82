"""Share of the traced slice in which no operation ran on the device:
1 - union of the device planes' operation intervals / the slice, from the
profiler's trace of the server process (``trace_reduce.py``)."""


def read(ctx, args: dict):
    trace = ctx.slice.get("trace") or {}
    if not trace.get("window_s") or "busy_s" not in trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
