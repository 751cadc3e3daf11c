"""The measured window over every training step completed in it (ms)."""


def read(ctx):
    steps = ctx.host.get("steps")
    if not steps:
        return None
    return ctx.host["window_s"] / steps * 1e3
