"""Device seconds per step in the ELL aggregation (Pallas gather kernels,
SDDMM, and the XLA gathers and scatter-adds of the backward), from the
trace, averaged over chips."""


def read(ctx):
    t = ctx.get("trace")
    if t is None or not t.class_s.get("agg"):
        return None
    return t.class_s["agg"] / ctx["steps"]
