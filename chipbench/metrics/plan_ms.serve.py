"""Host time of the plan layer (critical TMs, delta, the joint topology solve) per decided epoch: the program's ``serve.plan`` spans in the window, in ms/epoch."""


def read(ctx):
    spans = [e for e in ctx["obs"] if e["name"] == "serve.plan"]
    if not spans or not ctx["layer"]["epochs"]:
        return None
    return sum(e["dur_us"] for e in spans) * 1e-3 / ctx["layer"]["epochs"]
