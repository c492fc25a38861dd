"""Time of the §4.6 gate on a topology epoch (diff onto panels, drain
order, the batched PDHG solves of the old, new and drained capacities, the
decision): the mean of the program's ``serve.plan.transition`` spans in the
window, in s per gated topology epoch; None in a window with no gate."""

from chipbench import spans


def read(ctx):
    s = spans.named(ctx["obs"], "serve.plan.transition")
    return spans.total_us(s) * 1e-6 / len(s) if s else None
