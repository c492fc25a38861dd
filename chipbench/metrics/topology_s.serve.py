"""Time of a topology epoch's joint solve (HiGHS on the host), its
realization, capacities and transition gate: the mean of the program's
``serve.plan.topology`` spans in the window, in s per topology epoch; None
in a window with no topology epoch."""

from chipbench import spans


def read(ctx):
    s = spans.named(ctx["obs"], "serve.plan.topology")
    return spans.total_us(s) * 1e-6 / len(s) if s else None
