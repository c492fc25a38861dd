"""Host time of the score layer per decided epoch: the program's
``serve.score`` spans in the window less the ``*.wait`` spans they hold
(casts and padding, burst expansion, kernel dispatch), in ms/epoch."""

from chipbench import spans


def read(ctx):
    return spans.per_epoch_ms(spans.host_us(ctx["obs"], "serve.score"), ctx)
