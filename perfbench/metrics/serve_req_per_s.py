"""Requests answered inside the window over the window (req/s)."""


def read(ctx):
    if "answered_in_window" not in ctx.host:
        return None
    return ctx.host["answered_in_window"] / ctx.host["window_s"]
