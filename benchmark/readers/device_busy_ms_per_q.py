"""Device busy time (union of op intervals in the capture) per request the
capture covers.  Nothing traced, or no request inside the capture: None."""


def read(ctx, params):
    trace, traced = ctx.get("trace"), ctx.get("traced")
    if not trace or not traced or traced["requests"] <= 0:
        return None
    return 1e3 * trace["busy_s"] / traced["requests"]
