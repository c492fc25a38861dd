"""PDHG iterations of the §4.6 gate per evaluation in the window: stages
1 + 2 + 3 of every real element of its batch (old, new and each drain
stage) and its cold anchor solves, from the solver's own counters, in
iters/evaluation; None in a window whose topology changes needed no moves,
or for a driver that reports no gate."""


def read(ctx):
    gate = ctx["layer"].get("gate")
    if not gate or not gate["evaluations"]:
        return None
    return gate["pdhg_iters"] / gate["evaluations"]
