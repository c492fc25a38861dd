"""Host time of the solve layer per decided epoch: the program's
``serve.solve`` spans in the window less the ``*.wait`` spans they hold
(inputs, PDHG dispatch, fallback check, weight build), in ms/epoch."""

from chipbench import spans


def read(ctx):
    return spans.per_epoch_ms(spans.host_us(ctx["obs"], "serve.solve"), ctx)
