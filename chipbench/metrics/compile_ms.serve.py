"""Compile time inside the window per decided epoch: the program's
``jax.compile`` events (backend compiles and persistent-cache reads), in
ms/epoch; 0.0 when nothing compiled, None for a program that records no
``serve.epoch`` spans (and so no compile events either)."""

from chipbench import spans


def read(ctx):
    if not spans.named(ctx["obs"], "serve.epoch"):
        return None
    return spans.per_epoch_ms(
        spans.total_us(spans.named(ctx["obs"], "jax.compile")), ctx)
