"""Time of the critical-TM selection (k-means over the 7-day window, on the
host) per decided epoch: the program's ``serve.plan.critical_tms`` spans in
the window, in ms/epoch."""

from chipbench import spans


def read(ctx):
    s = spans.named(ctx["obs"], "serve.plan.critical_tms")
    return spans.per_epoch_ms(spans.total_us(s) if s else None, ctx)
