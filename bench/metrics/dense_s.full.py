"""Device seconds per step outside the aggregation and the collectives
(matrix products, elementwise work, loss and SGD), from the trace,
averaged over chips."""


def read(ctx):
    t = ctx.get("trace")
    if t is None or not t.class_s.get("dense"):
        return None
    return t.class_s["dense"] / ctx["steps"]
