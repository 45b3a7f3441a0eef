"""The slowest requests of the window, from the server's own account of
each (`/debug/vars` `tail`: per route the 16 slowest requests since the
mark the harness sets just before `vars0`; utils/tracing.py).

`params.routes`: the tails of these routes, merged.  A record counts when
its `seq` (the route's `http/<route>_count` when the request closed) is
past `vars0`'s count: it closed inside the window.  `params.stat`:

  slowest_ms       the largest `ns`: the slowest request on the server's
                   own clock, to set beside the client's slowest
  ms_per_q         sum of `ns` / `client/completed`: how much of a mean is
                   its slowest requests
  share            100 x sum of `params.fields` (members of a record, or
                   `stages/<name>`) / sum of `ns`
  trim_ms_per_q    (the delta of `params.counter` - what the tail's
                   requests spent in the stage `params.stage`)
                   / (`client/completed` - the tail's size): the mean of a
                   stage over the requests a stall cannot have held
  pulse_late_max_ms  `runtime/pulse_late_max_ns` at the window's end (the
                   mark reset it)

A program without the tail or the pulse (the parent commit): nothing to
read."""

from harness.metrics import counter


def window_tail(ctx: dict, routes: list[str]) -> list[dict] | None:
    tail = ctx["vars1"].get("tail")
    if tail is None:
        return None
    return [rec for route in routes for rec in tail.get(route, [])
            if rec["seq"] > counter(ctx["vars0"], f"http/{route}_count")]


def _field(rec: dict, path: str) -> float:
    if path.startswith("stages/"):
        return float(rec["stages"].get(path[len("stages/"):], (0,))[0])
    return float(rec[path])


def read(ctx, params):
    stat = params["stat"]
    if stat == "pulse_late_max_ms":
        late = ctx["vars1"].get("runtime", {}).get("pulse_late_max_ns")
        return None if late is None else late / 1e6
    recs = window_tail(ctx, params["routes"])
    if not recs:
        return None
    total = sum(rec["ns"] for rec in recs)
    done = counter(ctx["vars1"], "client/completed") \
        - counter(ctx["vars0"], "client/completed")
    if stat == "slowest_ms":
        return max(rec["ns"] for rec in recs) / 1e6
    if stat == "ms_per_q":
        return total / 1e6 / done if done > 0 else None
    if stat == "share":
        return 100.0 * sum(_field(rec, f) for rec in recs
                           for f in params["fields"]) / total
    if stat == "trim_ms_per_q":
        rest = done - len(recs)
        if rest <= 0:
            return None
        whole = counter(ctx["vars1"], params["counter"]) \
            - counter(ctx["vars0"], params["counter"])
        held = sum(_field(rec, "stages/" + params["stage"]) for rec in recs)
        return max(whole - held, 0.0) / 1e6 / rest
    raise ValueError(f"tail reader: unknown stat {stat!r}")
