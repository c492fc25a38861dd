"""Time the host waited on the device per decided epoch: the program's
``*.wait`` spans in the window (each holds only a device-to-host copy of a
PDHG stage's or a scoring kernel's outputs), in ms/epoch."""

from chipbench import spans


def read(ctx):
    w = spans.waits(ctx["obs"])
    return spans.per_epoch_ms(spans.total_us(w) if w else None, ctx)
