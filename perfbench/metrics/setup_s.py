"""Seconds from process start to the first timed step or request:
inputs and weights made, the index built, every program compiled or
loaded from the cache, and the warm-up run."""


def read(ctx):
    return ctx.setup_s
