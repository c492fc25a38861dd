"""Decided epochs in the window whose PDHG stage 1 stopped at the iteration
cap rather than at its duality gap, from the solver's own counters, in
epochs.  Such an epoch's u* carries no certificate, and its solve is the
slowest there is."""


def read(ctx):
    layer = ctx["layer"]
    if not layer["epochs"]:
        return None
    return layer["capped"]["stage1"]
