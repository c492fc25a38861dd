"""Time of the score layer (Pallas link-load and queue-loss kernels on the finished epoch's block) per decided epoch: the program's ``serve.score`` spans in the window, in ms/epoch."""


def read(ctx):
    spans = [e for e in ctx["obs"] if e["name"] == "serve.score"]
    if not spans or not ctx["layer"]["epochs"]:
        return None
    return sum(e["dur_us"] for e in spans) * 1e-3 / ctx["layer"]["epochs"]
