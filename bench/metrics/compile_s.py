"""Host seconds in ``lower_step(state).compile()`` of the training step,
on the host clock (a cache load once the compile cache holds it)."""


def read(ctx):
    return ctx["host"].get("compile_s")
