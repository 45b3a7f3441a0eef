"""Share of its roofline that the device work of the traced statements
reached: the least time the chip needs for the bytes and operations those
statements need (harness/peaks.py, by the `device_work.needs` of the
traffic file), over the summed device time of every operation the capture
holds.  The statements inside the capture are counted by the share of each
request's time that the capture covers, so with few, long requests the
share is only as exact as that count (PERF.md)."""

from harness import peaks


def read(ctx, params):
    trace, traced = ctx.get("trace"), ctx.get("traced")
    if not trace or not traced or not traced["needs"] or not ctx.get("peaks"):
        return None
    kernel_s = sum(trace["program_s"].values())
    if kernel_s <= 0 or traced["points"] <= 0:
        return None
    need = peaks.NEEDS[traced["needs"]](traced["points"], traced["groups"])
    return 100.0 * peaks.least_seconds(need, ctx["peaks"]) / kernel_s
