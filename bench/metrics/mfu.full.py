"""The whole step's share of the chips' peak, in %: the model's FLOPs per
full-graph step (`bench/counts`, forward and backward) times the steps in
the window, over the window's host seconds, the chips and the bf16 peak."""


def read(ctx):
    if not ctx.get("peaks"):
        return None
    if not ctx.get("flops_per_step") or ctx["window_s"] <= 0:
        return None
    rate = ctx["flops_per_step"] * ctx["steps"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
