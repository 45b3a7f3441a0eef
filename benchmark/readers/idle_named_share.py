"""Of the time in the capture's longest idle gaps, the % that
`trace_reduce.attribute` put down to a span of the program: a label that
holds `ogt:` (utils/tracing.py writes each span into the capture as
`ogt:<stage>`).  The rest is named by a frame of the runtime, or by
nothing.  No gaps, or a program without such spans and a capture without
them: nothing to read."""


def read(ctx, params):
    trace = ctx.get("trace")
    gaps = (trace or {}).get("idle_gaps") or []
    total = sum(s for _, s in gaps)
    if total <= 0 or not any("ogt:" in label for label, _ in gaps):
        return None
    return 100.0 * sum(s for label, s in gaps if "ogt:" in label) / total
