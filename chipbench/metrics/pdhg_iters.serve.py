"""PDHG iterations (stages 1 + 2 + 3) per decided epoch in the window, from
the solver's own counters, in iters/epoch."""


def read(ctx):
    layer = ctx["layer"]
    if not layer["epochs"]:
        return None
    return layer["pdhg_iters"] / layer["epochs"]
