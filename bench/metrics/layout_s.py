"""Host seconds in the engine's constructor (`DistGNNEngine.__init__`: the
partition, ELL tables and exchange plan), on the host clock."""


def read(ctx):
    return ctx["host"].get("layout_s")
