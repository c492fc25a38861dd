"""Time of the solve layer (warm-started PDHG routing solve, weight install) per decided epoch: the program's ``serve.solve`` spans in the window, in ms/epoch."""


def read(ctx):
    spans = [e for e in ctx["obs"] if e["name"] == "serve.solve"]
    if not spans or not ctx["layer"]["epochs"]:
        return None
    return sum(e["dur_us"] for e in spans) * 1e-3 / ctx["layer"]["epochs"]
