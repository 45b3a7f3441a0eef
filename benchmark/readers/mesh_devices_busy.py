"""The device planes of the capture on which an operation ran
(`trace_reduce.reduce_planes` `devices_busy`).  Nothing traced: None."""


def read(ctx, params):
    trace = ctx.get("trace")
    return None if not trace else trace.get("devices_busy")
