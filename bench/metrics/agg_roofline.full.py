"""The aggregation's share of its memory roofline, in %: the compulsory
bytes of a step's aggregation calls on one chip (`bench/counts`) over the
chip's HBM bandwidth, divided by the traced aggregation seconds."""


def read(ctx):
    if not ctx.get("peaks"):
        return None
    t = ctx.get("trace")
    if t is None or not t.class_s.get("agg"):
        return None
    agg_s = t.class_s["agg"] / ctx["steps"]
    least = ctx["agg_bytes_per_chip"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / agg_s
