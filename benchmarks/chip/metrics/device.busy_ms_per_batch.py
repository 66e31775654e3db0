"""Device busy time (union of op intervals) in the traced window, in
milliseconds, over the batches completed in it."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.batches:
        return None
    return tr.busy_s * 1e3 / tr.batches
