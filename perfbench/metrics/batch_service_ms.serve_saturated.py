"""The measured window over the batches the engine finished in it (ms):
prepare, device run and finalize of one padded batch, from the client's
side, while the queue never empties."""


def read(ctx):
    n = ctx.host.get("batches_in_window")
    if not n:
        return None
    return ctx.host["window_s"] / n * 1e3
